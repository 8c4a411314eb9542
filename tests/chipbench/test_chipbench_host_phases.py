"""Eleven per-layer metrics that WOULD read the program's host phases
(PR 58) through the readers that are there, as ISSUE 58 words them. They
are not in the benchmark: a cell reports the metrics its own file lists, so
they join by an edit of the ten cells' files, which is a ``benchmark`` PR's
(``PERF.md`` section 7). What this holds meanwhile: the accepted readers
find what the program writes, under the names and the parameters below; a
program without the phases reads nothing there; and a gap inside a pass is
named by the phase that covers it, with no edit of ``xprof``."""

import math

import pytest

from chipbench import manifest, registry, xprof
from chipbench.observe import Observations
from dynamo_tpu.engine.compile_cache import CompileStats
from dynamo_tpu.engine.flight_recorder import (
    PHASES,
    START_PHASES,
    FlightRecorder,
)
from test_chipbench_xprof import _ms, made_trace

WAITS = ("idle", "retire_wait", "handoff_wait")
#: the engine thread's own work: every phase but the three waits, and other
OWN = [f"host_{p}_ms" for p in PHASES if p not in WAITS] + ["host_other_ms"]


def _share(over, layer, better="lower"):
    return {"unit": "%", "better": better, "layer": layer,
            "moves": "out_tok_s_chip", "reader": "flight_ratio",
            "params": {"over": over, "under": ["host_period_ms"],
                       "scale": 100.0}}


def _start(key):
    return {"unit": "s", "better": "lower", "layer": "runner",
            "moves": "setup_s", "reader": "readiness_peak",
            "params": {"key": key}}


#: what a metric's file would hold (``source`` is ``program_counter`` for all)
PROPOSED = {
    "scheduler.host_step_p50_ms": {
        "unit": "ms", "better": "lower", "layer": "scheduler",
        "moves": "out_tok_s_chip", "reader": "flight",
        "params": {"fields": OWN, "stat": "p50"}},
    "scheduler.host_busy_pct": _share(OWN, "scheduler"),
    "scheduler.device_wait_pct": _share(
        ["host_retire_wait_ms"], "scheduler", better="higher"),
    "scheduler.side_channels_pct": _share(
        ["host_side_channels_ms"], "scheduler"),
    "frontend.handoff_wait_pct": _share(
        ["host_handoff_wait_ms"], "HTTP frontend"),
    "runner.pack_pct": _share(["host_pack_ms"], "runner"),
    "runner.start_runtime_s": _start("start_runtime_seconds"),
    "runner.start_weights_s": _start("start_weights_seconds"),
    "runner.warmup_tracing_s": _start("warmup_tracing_seconds_total"),
    "runner.warmup_lowering_s": _start("warmup_lowering_seconds_total"),
    "runner.warmup_backend_s": _start("warmup_backend_seconds_total"),
}
ELEVEN = list(PROPOSED)
STEP, START = ELEVEN[:6], ELEVEN[6:]
BENCH = manifest.benchmark_json()


def read_metrics(names, obs):
    """``harness.read_metrics``, of definitions that have no file yet."""
    out = {}
    for name in names:
        m = PROPOSED[name]
        value = registry.load("readers", m["reader"]).read(obs, **m["params"])
        if value is not None:
            out[name] = {"value": value, "unit": m["unit"]}
    return out


def records(host_of_step):
    """Flight records as the program writes them, one a mapping of seconds
    by phase (None: a program that has no phases)."""
    rec = FlightRecorder(capacity=64)
    for host in host_of_step:
        rec.note_step("unified", decode_tokens=8, dispatch_ms=1.0, host=host)
    steps = rec.snapshot()
    if host_of_step and host_of_step[0] is None:
        steps = [{k: v for k, v in r.items() if not k.startswith("host_")}
                 for r in steps]
    return steps


def observations(flight, readiness=()):
    return Observations(
        window=(100.0, 151.0), chips=1, setup_s=60.0, records=[],
        flight=flight, readiness=list(readiness),
    )


#: a 20 ms period: 4 ms waited for the device, 2 for the loop, 1 idle
HOST = dict(idle=0.001, drain=0.0005, retire_wait=0.004, retire=0.003,
            handoff_wait=0.002, admit=0.0005, compose=0.002, pack=0.0025,
            put=0.0005, dispatch=0.001, side_channels=0.002, other=0.001)
SNAP = {"start_runtime_seconds": 7.5, "start_weights_seconds": 21.0,
        "start_build_seconds": 1.0, "start_warmup_seconds": 30.0,
        "warmup_tracing_seconds_total": 6.0,
        "warmup_lowering_seconds_total": 4.0,
        "warmup_backend_seconds_total": 19.0}


def test_each_of_the_eleven_reads_a_float_from_what_the_program_writes():
    assert math.isclose(sum(HOST.values()), 0.020)
    obs = observations(
        records([HOST, HOST, dict(HOST, pack=0.0125)]),
        [dict(SNAP, t=110.0), dict(SNAP, t=112.0)],
    )
    read = read_metrics(ELEVEN, obs)
    got = {k: v["value"] for k, v in read.items()}
    assert sorted(got) == sorted(ELEVEN)
    assert all(isinstance(v, float) for v in got.values())
    # the thread's own work: the period less the three waits
    assert math.isclose(got["scheduler.host_step_p50_ms"], 13.0)
    busy, whole = 13.0 * 3 + 10.0, 20.0 * 3 + 10.0
    assert math.isclose(got["scheduler.host_busy_pct"], 100 * busy / whole)
    assert math.isclose(got["scheduler.device_wait_pct"], 100 * 12 / whole)
    assert math.isclose(got["frontend.handoff_wait_pct"], 100 * 6 / whole)
    assert math.isclose(got["scheduler.side_channels_pct"], 100 * 6 / whole)
    assert math.isclose(got["runner.pack_pct"], 100 * 17.5 / whole)
    assert (got["scheduler.host_busy_pct"] + got["scheduler.device_wait_pct"]
            + got["frontend.handoff_wait_pct"]) <= 100.0
    assert got["runner.start_runtime_s"] == 7.5
    assert got["runner.start_weights_s"] == 21.0
    assert [got[f"runner.warmup_{p}_s"]
            for p in ("tracing", "lowering", "backend")] == [6.0, 4.0, 19.0]
    units = {k: v["unit"] for k, v in read.items()}
    assert {units[m] for m in START} == {"s"}
    assert units["scheduler.host_step_p50_ms"] == "ms"


def test_a_program_without_the_phases_leaves_the_shares_out():
    """The parent of PR 58: its records carry no ``host_*_ms``, its
    ``readiness()`` no ``start_*_seconds``. Nothing raises; the five
    shares and the two start metrics are left out of the line. The
    accepted ``flight`` reader sums absent fields as 0, so the p50 reads
    0.0 there; the three warm-up counters are PR 50's and are read."""
    old = {k: v for k, v in SNAP.items() if k.startswith("warmup_")}
    obs = observations(records([None] * 3), [dict(old, t=110.0)])
    got = read_metrics(ELEVEN, obs)
    assert sorted(got) == sorted(
        ["scheduler.host_step_p50_ms"] + [m for m in START if "warmup" in m])
    assert got["scheduler.host_step_p50_ms"]["value"] == 0.0
    # and no record at all reads nothing at all
    assert read_metrics(STEP, observations([])) == {}


def test_a_gap_inside_a_pass_is_named_by_the_phase_that_covers_it():
    trace = made_trace()
    host = next(p for p in trace["planes"] if p["name"] == "/host:CPU")
    # the engine's thread, in a lane of its own: a pass that covers the gap
    # between the two programs (10 .. 14 ms), its side channels inside it
    host["lines"].append({"name": "tpu-engine", "events": [
        ["engine/pass", _ms(9.75), _ms(4.5)],
        ["engine/side_channels", _ms(11.5), _ms(1.0)],
    ]})
    gaps = dict(xprof.reduce(trace, chips=1)["idle_gaps"])
    assert math.isclose(
        gaps["between_programs__host_in_engine_side_channels"], 0.004)
    assert "between_programs__host_in_shard_args" not in gaps
    # a gap no phase covers is still the engine's, not "outside the runtime"
    host["lines"][-1]["events"].pop()
    host["lines"][0]["events"].pop()        # the runtime's shard_args
    gaps = dict(xprof.reduce(trace, chips=1)["idle_gaps"])
    assert math.isclose(gaps["between_programs__host_in_engine_pass"], 0.004)


@pytest.mark.parametrize("name", ELEVEN)
def test_a_definition_names_what_the_program_writes(name):
    """The field or the key is one the program writes, and the entry would
    pass the manifest's rules beside the metrics that are there."""
    m = PROPOSED[name]
    assert manifest.NAME.match(name) and manifest.UNIT.match(m["unit"])
    assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    assert m["layer"] in {e["layer"] for e in BENCH["per_layer"]}
    assert name not in {e["name"] for e in BENCH["per_layer"]}
    assert callable(registry.load("readers", m["reader"]).read)
    if name in START:
        written = {f"start_{p}_seconds" for p in START_PHASES} | set(
            CompileStats().snapshot())
        assert m["params"]["key"] in written
        return
    rec = FlightRecorder(capacity=8)
    rec.note_step("unified")
    written = set(rec.snapshot()[0])
    params = m["params"]
    fields = params.get("fields") or params["over"] + params["under"]
    assert set(fields) <= written
