"""``scopes.py`` on the saved v5e trace of the GPT-2 cell: it agrees with
``spans.py`` where both read (the segment ``head``), reads a kernel's scope,
and reads nothing — without raising — for scopes that trace's program does
not have, which is what the parent of a PR that adds a scope looks like."""

import gzip
import os
import shutil

import pytest

import scopes
import spans

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures", "train-b8.v5e.xplane.pb.gz")


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """A run record that points at the fixture, unpacked where ``find_xplane`` looks."""
    d = tmp_path_factory.mktemp("profile")
    with gzip.open(FIXTURE, "rb") as src, open(d / "t.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    return {"profile_dir": str(d), "trace": {}, "chips": 1,
            "workload": {"traffic": {"rows": 8}, "kernel_names": {"full_attn": ["attn/attn_kernel"]}},
            "model": {"max_seq_len": 1024}}


def test_head_scope_agrees_with_spans(run):
    want = spans.reduce_rows(spans.rows_from_xspace(spans.read_xspace(FIXTURE)))["metrics"]["head_ce_ms"]
    assert scopes.scope_ms(run, ["head"]) == pytest.approx(want, rel=1e-9)


def test_nested_scope_is_part_of_its_parent(run):
    attn = scopes.scope_ms(run, ["attn"])
    kernel = scopes.scope_ms(run, ["attn/attn_kernel"])
    assert 0 < kernel < attn
    # either name reads the union, once
    assert scopes.scope_ms(run, ["attn", "attn/attn_kernel"]) == pytest.approx(attn)


@pytest.mark.parametrize("metric", ["gdn_ms.train", "moe_ms.train", "gdn_roofline.train",
                                    "moe_experts_roofline.train"])
def test_a_program_without_the_scope_reads_nothing(run, metric):
    import run as harness

    assert harness.load_module("metrics", metric).read(run) is None


def test_counter_readers_read_nothing_without_their_events(run):
    import run as harness

    for metric in ("moe_dropped.train", "moe_load_max_over_mean.train"):
        assert harness.load_module("metrics", metric).read({**run, "events": [{"etype": "step"}]}) is None
