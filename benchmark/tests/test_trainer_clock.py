"""The readers of the trainer's ``startup`` and ``slow_step`` events, over
event lists written by hand: a number for every one on a run of a tree that
has the events, 0 for the slow-step four on a run without a slow step, None
for all thirteen on the events of a tree without them (the parent), and the
steps the profiler touched left out."""

import pytest

import run as harness

STARTUP = ("setup_before_train_s.train", "setup_state_s.train", "setup_first_step_s.train",
           "setup_warmup_rest_s.train", "setup_trace_lower_s.train", "setup_cache_load_s.train",
           "setup_compile_s.train", "setup_cache_misses.train", "setup_named_pct.train")
SLOW = ("slow_steps.train", "slow_step_block_ms.train", "slow_step_host_ms.train",
        "slow_step_host_late_ms.train")


def read(name, run):
    return harness.load_module("metrics", name).read(run)


def startup_event():
    return {
        "etype": "startup", "t_enter": 117.5, "loop_began": True,
        "phases": {"distributed": [0.0, 0.1], "mesh": [0.1, 0.2], "model": [0.3, 0.1],
                   "state": [0.4, 2.0], "restore": [2.4, 0.0], "step_build": [2.4, 0.05],
                   "data": [2.45, 0.3], "obs": [2.5, 0.05], "eval_setup": [2.55, 0.1],
                   "warmup_first": [2.9, 4.0], "warmup_rest": [6.9, 1.0]},
        "named_s": 7.9, "total_s": 8.0, "trace_s": 1.5, "lower_s": 0.5,
        "backend_compile_s": 3.0, "cache_retrieval_s": 2.25, "compiles": 40,
        "cache_hits": 3, "cache_misses": 37, "cache_writes": 0, "compiled": {},
    }


def step_event(step, **more):
    return {"etype": "step", "step": step, "step_time_s": 0.5, "block_s": 0.49,
            "between_s": 0.001, **more}


def slow_event(step, block, host_each, late):
    e = {"etype": "slow_step", "step": step, "held_by": "block", "owner": "device_or_driver",
         "excess_s": block + 5 * host_each, "block_excess_s": block, "host_late_s": late}
    for p in ("data_wait", "rng", "launch", "other", "between"):
        e[f"{p}_excess_s"] = host_each
    return e


def make_run(events, traced=False):
    return {"events": events, "t_process": 100.0, "t_window": 125.6,
            "profile_dir": "/somewhere" if traced else None,
            "workload": {"trace_steps": [10, 16]}}


def test_the_four_parts_of_setup_add_up_to_what_the_phases_name():
    run = make_run([startup_event()])
    got = {name: read(name, run) for name in STARTUP}
    assert got["setup_before_train_s.train"] == pytest.approx(17.5)
    assert got["setup_state_s.train"] == pytest.approx(2.4)
    assert got["setup_first_step_s.train"] == pytest.approx(4.05)
    assert got["setup_warmup_rest_s.train"] == pytest.approx(1.45)
    parts = sum(got[k] for k in STARTUP[:4])
    assert parts == pytest.approx(17.5 + 7.9)
    # but for what no phase names, that is setup_s of the same run
    setup_s = read("setup_s", run)
    assert setup_s - parts == pytest.approx(8.0 - 7.9 + 0.1)
    assert got["setup_trace_lower_s.train"] == pytest.approx(2.0)
    assert got["setup_cache_load_s.train"] == pytest.approx(2.25)
    assert got["setup_compile_s.train"] == pytest.approx(0.75)
    assert got["setup_cache_misses.train"] == 37
    assert got["setup_named_pct.train"] == pytest.approx(98.75)


def test_a_phase_the_reader_does_not_know_lands_in_the_third_part():
    ev = startup_event()
    ev["phases"]["something_new"] = [7.0, 0.5]
    ev["named_s"] += 0.5
    run = make_run([ev])
    assert read("setup_warmup_rest_s.train", run) == pytest.approx(1.95)


@pytest.mark.parametrize("name", STARTUP + SLOW)
def test_a_parents_events_read_none(name):
    # the parent's step events carry no cpu_s and it emits no startup event
    run = make_run([step_event(s) for s in range(1, 30)])
    assert read(name, run) is None


@pytest.mark.parametrize("name", SLOW)
def test_a_run_without_a_slow_step_reads_zero(name):
    run = make_run([startup_event()] + [step_event(s, cpu_s=0.01) for s in range(1, 30)])
    assert read(name, run) == 0


def test_slow_steps_are_summed_by_block_host_and_lateness():
    events = [step_event(s, cpu_s=0.01) for s in range(1, 30)]
    events += [slow_event(7, block=1.76, host_each=0.002, late=1.74),
               slow_event(21, block=0.05, host_each=0.01, late=0.0)]
    run = make_run(events)
    assert read("slow_steps.train", run) == 2
    assert read("slow_step_block_ms.train", run) == pytest.approx(1810.0)
    assert read("slow_step_host_ms.train", run) == pytest.approx(60.0)
    assert read("slow_step_host_late_ms.train", run) == pytest.approx(1740.0)


def test_the_profilers_steps_are_left_out():
    events = [step_event(s, cpu_s=0.01) for s in range(1, 30)]
    events += [slow_event(9, 0.03, 0.0, 0.0), slow_event(10, 0.1, 0.0, 0.0),
               slow_event(16, 13.0, 0.0, 0.0), slow_event(17, 0.04, 0.0, 0.0)]
    assert read("slow_steps.train", make_run(events)) == 4
    traced = make_run(events, traced=True)
    assert read("slow_steps.train", traced) == 2
    assert read("slow_step_block_ms.train", traced) == pytest.approx(70.0)


def test_every_new_metric_is_in_benchmark_json_for_every_cell():
    import json
    import os

    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in STARTUP:
        assert by_name[name]["layer"] == "trainer start-up" and by_name[name]["moves"] == "setup_s"
        assert "workloads" not in by_name[name]
    for name in SLOW:
        assert by_name[name]["layer"] == "trainer loop" and "workloads" not in by_name[name]
    assert by_name["slow_steps.train"]["moves"] == "train_step_ms_p95"
