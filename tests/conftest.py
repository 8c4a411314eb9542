"""Test harness config: force an 8-device virtual CPU mesh.

Multi-chip hardware is not available in CI; sharding/mesh tests run against
`--xla_force_host_platform_device_count=8` CPU devices, mirroring how the
reference tests distributed behavior without a cluster (reference:
lib/runtime/tests/common/mock.rs — in-process mock network).

The suite runs on the CPU backend whatever the machine holds: it compiles
thousands of tiny programs and compares interpret-mode kernels with their
XLA twins, none of which should queue for (or hold) a chip. The driver
exports JAX_PLATFORMS=cpu already; the config update below makes a bare
`pytest tests/` behave the same. The one path that needs the real chip
is `python chip_smoke.py`, not a test.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags = (_flags + " --xla_force_host_platform_device_count=8").strip()
if "xla_backend_optimization_level" not in _flags:
    # Tier-1 is compile-bound: the suite compiles thousands of tiny-model
    # XLA programs and runs each a handful of times, so LLVM optimization
    # passes dominate wall clock (measured ~35% of test_engine.py).
    # Correctness is opt-level-independent; tests comparing two runs do
    # so under the same flags. Production paths never see this.
    _flags = (_flags + " --xla_backend_optimization_level=0").strip()
os.environ["XLA_FLAGS"] = _flags

# Disable the persistent XLA compile cache's auto-resolution unless a test
# opts in (explicit EngineConfig.compile_cache_dir / monkeypatch): one
# CLI-path engine activating it would flip the process-global
# jax_compilation_cache_dir (entry-size/compile-time floors at 0) and every
# later compile in the suite would pay disk serialization for nothing.
# Unconditional assignment — an ambient value (the shipped container
# exports this var) must not leak into the suite either.
os.environ["DYNAMO_TPU_COMPILE_CACHE_DIR"] = "none"
# An ambient $JAX_COMPILATION_CACHE_DIR would win over every tmp_path a
# test passes (engine/compile_cache.py placed): tests opt in by
# monkeypatch so they behave the same everywhere.
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

import jax

jax.config.update("jax_platforms", "cpu")

import pytest


@pytest.fixture
def anyio_backend():
    return "asyncio"


# A benchmark test that holds only while its cell is the newest one. The
# file is the benchmark's and no program PR may edit it; the same facts are
# held, by the cell's NAME, where the reason says. A `benchmark` PR that
# repairs the test takes its line out of here.
_OUTLIVED = {
    "tests/chipbench/test_chipbench_sdar.py::"
    "test_manifest_holds_the_new_cell_and_nothing_is_inconsistent":
        "asserts that its configuration and cell are the LAST entries of "
        "BENCHMARK.json and that no later cell joined its metrics' lists; "
        "held by name in test_chipbench_ling.py::"
        "test_manifest_holds_the_cell_under_its_name[sdar-30b-a3b-l7.chat-c64]",
    "tests/chipbench/test_chipbench_brumby.py::"
    "test_manifest_holds_the_cell_and_what_it_brought":
        "asserts that BENCHMARK.json has eight cells and that its own is "
        "the LAST of every list it joined; held by name in "
        "test_chipbench_deepseek_v2.py::"
        "test_the_cell_before_still_holds_what_it_brought",
    "tests/chipbench/test_chipbench_deepseek_v2.py::"
    "test_the_cell_before_still_holds_what_it_brought":
        "asserts that no later cell joined state.slots_used_peak_pct, which "
        "the next cell with a state table in slots reports (PR 56, after "
        "review); every other fact of it is held by name in "
        "test_chipbench_nemotron_h.py::"
        "test_the_cells_before_still_hold_what_they_brought"
        "[brumby-14b-l8.longdoc-c20]",
    "tests/chipbench/test_chipbench_nemotron_h.py::"
    "test_the_cells_before_still_hold_what_they_brought"
    "[brumby-14b-l8.longdoc-c20]":
        "asserts that its own cell is the only one that joined "
        "state.slots_used_peak_pct behind Brumby's; the next cell with a "
        "state table reports it too (PR 61). Every other fact of it is "
        "held, with no cell's list pinned to its length, in "
        "test_chipbench_lfm2.py::"
        "test_the_cells_before_still_hold_what_they_brought"
        "[brumby-14b-l8.longdoc-c20]",
    **{
        "tests/chipbench/test_chipbench_host_phases.py::"
        f"test_a_definition_names_what_the_program_writes[{name}]":
            "asserts that the definition is NOT in the benchmark yet; the "
            "cell lfm2-24b-a2b-l10.chat-c128 lists it since PR 61. Every "
            "other fact of it is held in test_chipbench_lfm2.py::"
            f"test_a_host_metric_is_the_definition_that_was_proposed[{name}]"
        for name in (
            "scheduler.host_step_p50_ms", "scheduler.device_wait_pct",
            "scheduler.side_channels_pct", "frontend.handoff_wait_pct",
        )
    },
}


def pytest_configure(config):
    # The order below is the schedule: xdist's `loadfile` would sort the
    # files again by their count of tests alone.
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


def _starts_a_server(item) -> bool:
    """The test starts a whole server in a process of its own: through
    ``test_chipbench_run._run``, or as that function does."""
    run = getattr(item.module, "_run", None)
    return getattr(run, "__module__", None) == "test_chipbench_run" and bool(
        {"_run", "subprocess"} & set(item.function.__code__.co_names)
    )


def pytest_collection_modifyitems(items):
    for item in items:
        reason = _OUTLIVED.get(item.nodeid)
        if reason:
            item.add_marker(pytest.mark.skip(reason=reason))
    # Each file's tests together; the few, long tests that start a server
    # first, in their file and among the files (handed out last they ran
    # alone while every other worker sat idle; last in their file, the
    # file xdist queues behind a worker's last two tests waited them out);
    # then the rest by count of tests, largest first, in their order.
    files = {}
    for item in items:
        files.setdefault(item.nodeid.split("::")[0], []).append(item)
    groups = [
        sorted(group, key=lambda item: not _starts_a_server(item))
        for group in files.values()
    ]
    groups.sort(key=lambda g: (not _starts_a_server(g[0]), -len(g)))
    items[:] = [item for group in groups for item in group]
