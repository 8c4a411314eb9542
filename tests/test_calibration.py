"""Drift guards for the single-sourced transfer calibration.

The 21.7 GB/s batched-KV handoff rate is recorded in exactly ONE symbol —
``planner.calibration.HANDOFF_GBPS`` — and every consumer (the router's
network-aware selector, the G4 peer pricing law) must read it from
there. A re-calibration run edits one line; these tests fail if a copy
of the number has crept back in anywhere or a consumer stopped
following the symbol.
"""

from __future__ import annotations

import re
from pathlib import Path
from types import SimpleNamespace

import dynamo_tpu
from dynamo_tpu.planner import calibration as cal


def test_router_default_link_is_the_calibrated_channel():
    from dynamo_tpu.llm.kv_router.scheduler import KvRouterConfig

    assert KvRouterConfig().default_link_gbps == cal.HANDOFF_GBPS


def test_peer_pricing_fallback_is_the_calibrated_channel():
    from dynamo_tpu.block_manager.peer import PeerBlockClient

    drt = SimpleNamespace(primary_lease_id=0xAA)
    comp = SimpleNamespace(
        namespace=SimpleNamespace(name="kv"), name="tpu"
    )
    client = PeerBlockClient(drt, comp, None)
    # No measured pull EMA, no peer advertisement: the pricing law must
    # fall back to the recorded channel, byte-for-byte.
    assert client.effective_bps("nobody") == cal.HANDOFF_GBPS * 1e9


def test_handoff_rate_has_exactly_one_source():
    """No module other than planner/calibration.py may carry the
    literal — a second copy silently diverges on re-calibration."""
    root = Path(dynamo_tpu.__file__).parent
    literal = re.compile(r"(?<![\d.])21\.7(?![\d])")
    offenders = [
        str(p.relative_to(root.parent))
        for p in sorted(root.rglob("*.py"))
        if p.name != "calibration.py" and literal.search(p.read_text())
    ]
    assert offenders == [], (
        f"hardcoded 21.7 GB/s copies found (use "
        f"planner.calibration.HANDOFF_GBPS): {offenders}"
    )


def test_transfer_cost_model_uses_the_symbol():
    """calibration.handoff_seconds matches the closed form built from
    the two published symbols — the contract every pricing consumer
    (router selector, G4 peer client) replicates."""
    isl = 3000
    base = cal.handoff_seconds(isl)
    expected = cal.HANDOFF_FIXED_US / 1e6 + (
        isl * cal.kv_bytes_per_token(None)
    ) / (cal.HANDOFF_GBPS * 1e9)
    assert abs(base - expected) < 1e-12
