"""Multi-host (pod) support.

The reference is strictly single-process — no ``jax.distributed.initialize``
anywhere (SURVEY.md §2.2 "Multi-host"). Here multi-host is first-class:
initialize once at entry — BEFORE any other JAX API touches the backend —
then every process builds the same global mesh and feeds its local shard of
the batch (see ``data/prefetch.py``); logging and checkpoint writes happen
on process 0 only.
"""

from __future__ import annotations

import os

_initialized = False

#: Env knob overriding the coordinator-init timeout (seconds). Takes
#: precedence over TrainConfig.coordinator_timeout_s so an operator can
#: shorten a stuck pod's hang without editing configs.
TIMEOUT_ENV = "DTC_COORDINATOR_TIMEOUT_S"


#: Where compiled programs persist when the environment names no place:
#: one fixed directory inside the checkout. The path is part of the
#: cache key's surroundings — a directory that moves (a temp name, a
#: pid, a timestamp) never hits.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def configure_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache; call before the first
    compile (``main.py``, ``chip_smoke.py`` and ``benchmark/run.py`` do,
    first thing). Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
    itself and nothing is configured here, so the cache can be placed
    from outside; otherwise it lives at :data:`COMPILE_CACHE_DIR`."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)


def _resolve_timeout(timeout_s: int | None) -> int | None:
    """Effective coordinator timeout: env knob > config > jax default.
    ``0`` means "jax's default" in BOTH the env knob and the config (so an
    operator can unset a debugging override without unexporting the var);
    negative or non-integer values are ignored with a warning."""
    env = os.environ.get(TIMEOUT_ENV)
    if env:
        try:
            v = int(env)
        except ValueError:
            v = None
        if v is not None and v > 0:
            return v
        if v == 0:
            return None  # explicit "use jax's default", overriding config
        print(
            f"[dtc_tpu] WARNING: ignoring invalid {TIMEOUT_ENV}={env!r} "
            "(want an integer >= 0; 0 = jax's default)"
        )
    if timeout_s and timeout_s > 0:
        return timeout_s
    return None  # jax's default (300s)


def maybe_initialize_distributed(
    multihost: bool, timeout_s: int | None = None
) -> None:
    """Initialize the JAX distributed runtime when running multi-process.

    MUST be the first JAX-touching call of the process: probing any backend
    API (``jax.process_count()``, ``jax.devices()``, …) first initializes
    the local backend and makes ``jax.distributed.initialize()`` raise on a
    real pod. The gate is therefore env/config only — no JAX probes.

    ``timeout_s`` (config ``coordinator_timeout_s``; env
    ``DTC_COORDINATOR_TIMEOUT_S`` overrides) bounds how long a worker waits
    for the coordinator before failing — SURVEY §5: without it a typo'd
    coordinator address hangs every host for jax's full default and the
    eventual error never names the likely causes.

    Raises on failure when multi-host was explicitly requested (config):
    a pod where every host silently falls back to independent
    single-process training is far worse than a crash. When only the
    environment hints at a cluster (a coordinator address left set by
    some other tool), failure degrades to a warning + single-process —
    the config didn't ask for multi-host.
    """
    global _initialized
    if _initialized:
        return
    env_says_cluster = bool(
        os.environ.get("JAX_COORDINATOR_ADDRESS") or os.environ.get("COORDINATOR_ADDRESS")
    )
    if not (multihost or env_says_cluster):
        return
    import jax

    timeout = _resolve_timeout(timeout_s)
    kwargs = {} if timeout is None else {"initialization_timeout": timeout}
    try:
        jax.distributed.initialize(**kwargs)
    except RuntimeError as e:
        # The embedding program (a launcher, a test harness) may have
        # initialized the distributed runtime itself — that is success,
        # not failure.
        if "already initialized" not in str(e).lower():
            if multihost:
                raise RuntimeError(_init_failure_message(timeout)) from e
            raise
    except Exception as e:
        if multihost:
            raise RuntimeError(_init_failure_message(timeout)) from e
        print(
            "[dtc_tpu] WARNING: cluster env vars set but "
            "jax.distributed.initialize() failed; continuing single-process"
        )
        return
    _initialized = True


def _init_failure_message(timeout: int | None) -> str:
    coord = (
        os.environ.get("JAX_COORDINATOR_ADDRESS")
        or os.environ.get("COORDINATOR_ADDRESS")
        or "<auto-detected>"
    )
    return (
        "multi-host initialization failed "
        f"(coordinator={coord}, timeout={timeout or 'jax default (300s)'}s). "
        "Common causes: wrong/unreachable coordinator address, a process "
        "count mismatch (a host never joined), or a firewall blocking the "
        "coordinator port. Set coordinator_timeout_s in the train config "
        f"or {TIMEOUT_ENV} to fail faster while debugging."
    )


def is_lead_process() -> bool:
    import jax

    return jax.process_index() == 0
