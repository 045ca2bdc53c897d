"""graftcost-modeled stage costs for the scheduler's pipeline mapper.

The front-end and fused-Tier-1 programs' cost fingerprints from the
checked-in manifest (``.graftaudit-manifest.json``, written by
``--write-manifest``), rooflined through :mod:`..analysis.graftcost`'s
machine model of the live backend. Only their ratio matters to the
mapper, so canonical-variant costs are enough.
"""
from __future__ import annotations

import json
from pathlib import Path


def _machine():
    """graftcost machine model of the live backend, keyed by its
    ``device_kind``; raises ``graftcost.UnknownDevice`` for a kind with
    no model."""
    import jax

    from ..analysis import graftcost

    return graftcost.machine_for_device_kind(jax.devices()[0].device_kind)


def _roofline(cost: dict, machine) -> float:
    return (max(cost.get("flops", 0) / machine.peak_flops,
                cost.get("hbm_bytes", 0) / machine.hbm_bytes_per_s)
            + cost.get("scan_depth", 0) * machine.seq_step_s)


def modeled_stage_costs() -> tuple | None:
    """(front_end_seconds, fused_t1_seconds) for the scheduler's
    bi-criteria pipeline mapper, or None when the manifest is
    unavailable; an unknown device raises. The front-end stage is the
    cxd-mode program
    (``frontend.cxd/...``) and the Tier-1 stage the fused CX/D+MQ
    program (``cxdmq.fused/...``, non-pallas — the portable variant the
    CPU mesh actually runs); both are rooflined through the same
    machine model. Absolute scale cancels in the mapper's ratios, so
    canonical-variant costs are exactly enough."""
    manifest = (Path(__file__).resolve().parents[2]
                / ".graftaudit-manifest.json")
    try:
        data = json.loads(manifest.read_text(encoding="utf-8"))
    except (OSError, json.JSONDecodeError):
        return None
    machine = _machine()
    front = t1 = None
    for key, rec in data.get("programs", {}).items():
        cost = rec.get("cost")
        if not cost:
            continue
        if key.startswith("frontend.cxd/") and front is None:
            front = _roofline(cost, machine)
        elif key.startswith("cxdmq.fused/") and \
                not key.startswith("cxdmq.fused.pallas/") and t1 is None:
            t1 = _roofline(cost, machine)
    if front is None or t1 is None or front <= 0 or t1 <= 0:
        return None
    return front, t1

