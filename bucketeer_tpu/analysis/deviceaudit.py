"""deviceaudit: compiled-artifact audit of the jitted codec programs.

graftlint's AST rules reason about *source*; this layer reasons about
what XLA actually compiles. Every registered jitted entry point (the
encode front-end's rows/cxd variants, the standalone sample transform,
the CX/D scan in both its jnp and Pallas-interpret forms, the decode
inverse and windowed region inverse, the compaction gather) is lowered
via ``jax.jit(...).lower(...)`` for a canonical power-of-two bucket
shape — on CPU, no device needed — and the StableHLO text is inspected
for facts the AST cannot see:

- **donation effectiveness** — an arg is donated only if the lowered
  entry carries ``tf.aliasing_output`` on it. JAX/XLA silently drop a
  requested donation whose aval matches no output (dtype or axis-order
  mismatch); the audit fails when a program's *declared* donate spec
  (the ``*_program`` seam each codec module exports) does not lower to
  a real alias, and, symmetrically, flags a donation recorded as
  "unusable" that would in fact alias (stale claim). This is how the
  repo knows its donation story is factual: the front-end and inverse
  donations PR 6 requested were verified dropped here and removed.
- **no host round-trips** — host callbacks (``xla_python_cpu_callback``
  and friends), infeed/outfeed and send/recv inside a device program
  are hard failures. Together with the d2h whitelist this pins the
  fact that device↔host traffic happens only at the sanctioned seams.
- **dtype hygiene** — any ``f64`` tensor type in a lowered program
  fails; ``stablehlo.convert`` churn is recorded in the manifest so
  drift (a new promotion sneaking into a hot program) fails CI.
- **program manifest** — ``.graftaudit-manifest.json`` records, per
  program × bucket, a stable fingerprint (sha256 of the lowered text)
  plus an op histogram. ``--audit`` diffs against the checked-in file
  the way a benchmark compares a run with its parent — but statically,
  on every PR, with no device. Regenerate after an intentional change
  with ``python -m bucketeer_tpu.analysis --write-manifest``.

The d2h whitelist validation closes the loop from the other side:
since no audited program transfers mid-flight, every sanctioned name in
``rules_jax.D2H_SANCTIONED`` must still perform an explicit transfer
(``jax.device_get`` / ``np.asarray`` of a device value, or delegate to
another sanctioned function). A whitelisted function that no longer
transfers is reported stale (``stale-d2h-whitelist``, warning).
"""
from __future__ import annotations

import ast
import hashlib
import json
import re
import warnings
from dataclasses import dataclass, field
from pathlib import Path

from . import graftcost
from .findings import ERROR, WARNING, Finding

MANIFEST_NAME = ".graftaudit-manifest.json"

# Relative drift in a modeled cost field (flops / hbm_bytes /
# scan_depth / peak_live_bytes) beyond which the manifest gate fails —
# a kernel-tuning PR that silently doubles modeled HBM traffic fails CI
# here, with no bench run. Small churn (layout jitter, a constant
# folded differently) stays under it.
COST_DRIFT_TOLERANCE = 0.10

DONATION_DROPPED = "audit-donation-dropped"
STALE_DONATION = "audit-stale-donation-claim"
HOST_TRANSFER = "audit-host-transfer"
F64_IN_PROGRAM = "audit-f64"
MANIFEST_DRIFT = "audit-manifest-drift"
STALE_D2H = "stale-d2h-whitelist"

# custom_call targets that round-trip through the host mid-program.
_TRANSFER_CALL_RE = re.compile(
    r"custom_call\s+@([\w.\-]*(?:callback|infeed|outfeed|host_|"
    r"send|recv)[\w.\-]*)", re.IGNORECASE)
_TRANSFER_OP_RE = re.compile(r"\bstablehlo\.(infeed|outfeed|send|recv)\b")
# f64 in a *type* position (tensor<f64> / tensor<4x4xf64>) — a bare
# substring check would false-positive on hex constant payloads.
_F64_RE = re.compile(r"[<x]f64[>]")
_OP_RE = re.compile(r"=\s+\"?([a-z_]+\.[\w]+)")
_ALIAS_RE = re.compile(
    r"%arg(\d+):[^{)%]*\{[^}]*tf\.aliasing_output[^}]*\}")


@dataclass(frozen=True)
class AuditProgram:
    """One registered jitted entry point at one canonical bucket.

    ``build() -> (fn, declared_donate, example_args)`` — the traceable
    callable and donate spec come from the owning module's ``*_program``
    seam, so the lowered artifact is the shipped construction.
    ``probe_donate`` names the argnums the audit *forces* donation on
    to learn whether XLA could alias them; ``donate_reason`` explains
    why probe-only args are not declared: ``"unusable"`` (no matching
    output aval — verified here, and a *stale claim* if the probe ever
    shows an alias) or ``"lifetime"`` (the buffer outlives the launch —
    aliasing legality is irrelevant, never flagged).
    """
    name: str
    build: object
    probe_donate: tuple = (0,)
    donate_reason: str = "unusable"


@dataclass
class ProgramFacts:
    """Lowered-artifact facts for one audited program."""
    name: str
    fingerprint: str = ""
    n_ops: int = 0
    op_counts: dict = field(default_factory=dict)
    declared_donate: tuple = ()
    probe_donate: tuple = ()
    aliased: tuple = ()            # argnums XLA will actually alias
    transfers: tuple = ()          # host round-trip ops found
    f64: bool = False
    text: str = ""                 # lowered StableHLO (for dumps)
    skipped: str = ""              # non-empty: not lowerable here
    donate_reason: str = "unusable"
    cost: object = None            # graftcost.CostFacts (set by
                                   # run_programs; pure fn of ``text``)

    def stale_donation_claim(self) -> bool:
        """True when the probe shows XLA would alias an arg the seam
        records as donation-unusable; "lifetime" buffers are never
        donated on purpose, so aliasing legality is irrelevant."""
        if self.donate_reason != "unusable":
            return False
        return bool(set(self.aliased) - set(self.declared_donate))


def registry() -> list:
    """The canonical audited programs. One entry per (jitted entry
    point, representative bucket); shapes are the smallest power-of-two
    buckets of the shipping tile geometry so CPU lowering stays cheap
    while exercising the same program structure as production."""
    import jax
    import jax.numpy as jnp

    from ..codec import cxd, frontend
    from ..codec.decode import device as ddevice
    from ..codec.pipeline import make_plan, transform_program

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    entries = []

    plan_g = make_plan(64, 64, 1, 2, True, 8)
    p_g = frontend.layout_for(plan_g).P
    plan_c = make_plan(64, 64, 3, 2, False, 8)
    p_c = frontend.layout_for(plan_c).P

    entries.append(AuditProgram(
        "frontend.rows/gray8-lossless-64x64-L2/B1",
        lambda: frontend.frontend_program(plan_g, p_g)
        + ([sds((1, 64, 64, 1), jnp.int32)],)))
    entries.append(AuditProgram(
        "frontend.rows/rgb8-lossy-64x64-L2/B2",
        lambda: frontend.frontend_program(plan_c, p_c)
        + ([sds((2, 64, 64, 3), jnp.int32)],)))
    entries.append(AuditProgram(
        "pipeline.transform/gray8-lossless-64x64-L2/B1",
        lambda: transform_program(plan_g)
        + ([sds((1, 64, 64, 1), jnp.int32)],)))

    def cxd_args(n):
        # Block batch + per-block meta + the runtime fixed-point shift
        # (dynamic on purpose: lossless and lossy share one compile).
        return ([sds((n, 64, 64), jnp.int32)]
                + [sds((n,), jnp.int32)] * 5 + [sds((), jnp.int32)])

    entries.append(AuditProgram(
        "cxd.scan/L2/N1",
        lambda: cxd.cxd_program(2, pallas=False) + (cxd_args(1),)))
    entries.append(AuditProgram(
        "cxd.scan.pallas/L2/N1",
        lambda: cxd.cxd_program(2, pallas=True, interpret=True)
        + (cxd_args(1),)))
    # Fused device Tier-1 (the tensor codec's device backend): CX/D
    # context modeling chained straight into the MQ coder inside one
    # program, so the (N, max_syms) symbol buffer never exists in HBM
    # (the perf-hbm-roundtrip the old two-program chain carried). The
    # MQ half's trip count is the realized symbol cursor — a dynamic
    # while the static cost model reports as unknown_trips rather than
    # a readable depth.
    entries.append(AuditProgram(
        "cxdmq.fused/L2/N1",
        lambda: cxd.fused_program(2, pallas=False) + (cxd_args(1),)))
    entries.append(AuditProgram(
        "cxdmq.fused.pallas/L2/N1",
        lambda: cxd.fused_program(2, pallas=True, interpret=True)
        + (cxd_args(1),)))

    iplan_g = ddevice.make_inverse_plan(64, 64, 1, 2, True, 8, False,
                                        lambda lvl, name: 1.0)
    iplan_c = ddevice.make_inverse_plan(64, 64, 3, 2, False, 8, True,
                                        lambda lvl, name: 0.5)
    entries.append(AuditProgram(
        "decode.inverse/gray8-reversible-64x64-L2/B1",
        lambda: ddevice.inverse_program(iplan_g)
        + ([sds((1, 1, 64, 64), jnp.int32)],)))
    entries.append(AuditProgram(
        "decode.inverse/rgb8-irreversible-64x64-L2/B2",
        lambda: ddevice.inverse_program(iplan_c)
        + ([sds((2, 3, 64, 64), jnp.int32)],)))

    rplan = ddevice.make_region_plan(64, 64, 1, 2, True, 8, False,
                                     lambda lvl, name: 1.0,
                                     16, 48, 16, 48)

    def region_entry():
        fn, donate = ddevice.region_program(
            rplan.levels, rplan.steps, rplan.used_mct, rplan.bitdepth)
        hvs = tuple(sds((1, by1 - by0, bx1 - bx0), jnp.int32)
                    for _, _, by0, by1, bx0, bx1, _ in rplan.slots)
        return fn, donate, [hvs]

    entries.append(AuditProgram(
        "decode.region_inverse/gray8-reversible-64x64-L2/win32",
        region_entry))

    entries.append(AuditProgram(
        "frontend.gather/rows512/chunk4096",
        lambda: frontend.gather_program()
        + ([sds((84, 512), jnp.uint8), sds((4096,), jnp.int64)],),
        probe_donate=(), donate_reason="lifetime"))

    # Compressed-domain tensor delivery (bucketeer_tpu/tensor/): the
    # tensor codec's block packer (the staged limb buffer becomes the
    # HBM-resident CX/D input; donation verified unusable — reshape
    # changes the aval) and the coefficient dequantizer (Tier-1
    # half-magnitudes -> device-resident subband coefficients; input
    # donated on the reversible int32->int32 path, verified dropped on
    # the float32 path). The Tier-1 program the tensor codec chains
    # after the packer is the cxdmq.fused entry above — one program,
    # two workloads.
    from ..tensor import codec as tcodec
    from ..tensor import coeffs as tcoeffs

    entries.append(AuditProgram(
        "tensor.pack/B4",
        lambda: tcodec.pack_program()
        + ([sds((4 * 4096,), jnp.int32)],)))

    def dq_entry(reversible, deltas, shapes):
        def build():
            fn, donate = tcoeffs.dequant_program(reversible, deltas)
            return fn, donate, [sds(s, jnp.int32) for s in shapes]
        return build

    dq_shapes = ((1, 16, 16), (1, 16, 16), (1, 16, 16), (1, 16, 16),
                 (1, 32, 32), (1, 32, 32), (1, 32, 32))
    entries.append(AuditProgram(
        "decode.coeffs.dequant/gray-reversible-L2",
        dq_entry(True, (1.0,) * 7, dq_shapes),
        donate_reason="declared"))
    entries.append(AuditProgram(
        "decode.coeffs.dequant/gray-irreversible-L2",
        dq_entry(False, (0.5,) * 7, dq_shapes)))

    # Batch data plane (bucketeer_tpu/batches/): the merged dequant as
    # the scheduler's _launch_dequant actually runs it — the same
    # program with the group's images stacked along a leading batch
    # axis. Donation carries over verbatim: reversible stays
    # int32->int32 (declared, aliased per band), irreversible drops the
    # alias (float32 outputs match no input aval).
    from ..batches import batch_mesh_program

    def bdq_entry(reversible, deltas, shapes):
        def build():
            fn, donate = batch_mesh_program(reversible, deltas)
            return fn, donate, [sds(s, jnp.int32) for s in shapes]
        return build

    bdq_shapes = tuple((4,) + s for s in dq_shapes)
    entries.append(AuditProgram(
        "batch.assemble.dequant/gray-reversible-L2/B4",
        bdq_entry(True, (1.0,) * 7, bdq_shapes),
        donate_reason="declared"))
    entries.append(AuditProgram(
        "batch.assemble.dequant/gray-irreversible-L2/B4",
        bdq_entry(False, (0.5,) * 7, bdq_shapes)))
    return entries


def lower_program(entry: AuditProgram) -> ProgramFacts:
    """Lower one registered program and extract its artifact facts.
    Donation is forced for ``probe_donate`` args (union with the
    declared spec) so the lowering itself answers "could XLA alias
    this?"; the unusable-donation warning JAX emits for a failed probe
    is expected and silenced."""
    import jax

    facts = ProgramFacts(entry.name)
    try:
        fn, declared, args = entry.build()
        probe = tuple(sorted(set(declared) | set(entry.probe_donate)))
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            lowered = jax.jit(fn, donate_argnums=probe).lower(*args)
            text = lowered.as_text()
    except Exception as exc:  # pragma: no cover - env-dependent
        facts.skipped = f"{type(exc).__name__}: {exc}"
        return facts
    facts.text = text
    facts.fingerprint = hashlib.sha256(text.encode("utf-8")).hexdigest()
    ops: dict = {}
    for m in _OP_RE.finditer(text):
        ops[m.group(1)] = ops.get(m.group(1), 0) + 1
    facts.op_counts = dict(sorted(ops.items()))
    facts.n_ops = sum(ops.values())
    facts.declared_donate = tuple(declared)
    facts.probe_donate = probe
    facts.aliased = tuple(sorted(
        int(m.group(1)) for m in _ALIAS_RE.finditer(text)))
    transfers = [m.group(1) for m in _TRANSFER_CALL_RE.finditer(text)]
    transfers += [m.group(1) for m in _TRANSFER_OP_RE.finditer(text)]
    facts.transfers = tuple(sorted(set(transfers)))
    facts.f64 = bool(_F64_RE.search(text))
    return facts


def check_program(facts: ProgramFacts) -> list:
    """Findings for one program's lowered facts (empty = clean)."""
    loc = f"<deviceaudit:{facts.name}>"
    out = []
    if facts.skipped:
        return out
    for argnum in facts.declared_donate:
        if argnum not in facts.aliased:
            out.append(Finding(
                DONATION_DROPPED, loc, 0,
                f"arg {argnum} is declared donated but the lowered "
                "program carries no tf.aliasing_output for it — XLA "
                "silently dropped the donation (no output matches the "
                "input aval). Fix the program or record the donation "
                "as unusable in its *_program seam", ERROR))
    if facts.stale_donation_claim():
        stale = sorted(set(facts.aliased) - set(facts.declared_donate))
        out.append(Finding(
            STALE_DONATION, loc, 0,
            f"arg(s) {stale} are recorded as donation-unusable but the "
            "lowered program shows XLA would alias them — the claim is "
            "stale; declare the donation and reap the HBM saving",
            WARNING))
    if facts.transfers:
        out.append(Finding(
            HOST_TRANSFER, loc, 0,
            f"host round-trip op(s) inside the device program: "
            f"{list(facts.transfers)} — device programs must ship "
            "results through the sanctioned d2h seams only", ERROR))
    if facts.f64:
        out.append(Finding(
            F64_IN_PROGRAM, loc, 0,
            "f64 tensor type in the lowered program (TPUs emulate f64 "
            "at heavy cost; a silent promotion leaked past the AST "
            "float64-leak rule)", ERROR))
    return out


def run_programs(entries=None) -> list:
    """Lower every registered program; returns [ProgramFacts].

    Clears JAX's global trace/lowering caches first: StableHLO emission
    dedupes private helpers (``@_where`` and friends) by *cached jaxpr
    object identity*, so a warm cache from earlier work in the process
    (e.g. the test suite) can split one shared helper into two
    identical copies and shift every symbol after it — a different
    fingerprint for the same program. Cold caches make the lowering a
    pure function of the registry, matching the fresh-process CLI run
    that generated the checked-in manifest."""
    import jax

    jax.clear_caches()
    out = []
    for entry in (registry() if entries is None else entries):
        facts = lower_program(entry)
        facts.donate_reason = entry.donate_reason
        if not facts.skipped:
            # The static cost model (graftcost) is a pure function of
            # the lowered text; computing it here keeps the manifest's
            # cost fingerprints in lockstep with the structural ones.
            facts.cost = graftcost.cost_program(facts.text, facts.name)
        out.append(facts)
    return out


# --- manifest ------------------------------------------------------------

def manifest_from_facts(all_facts: list) -> dict:
    import jax
    programs = {}
    for f in all_facts:
        if f.skipped:
            continue
        programs[f.name] = {
            "fingerprint": f.fingerprint,
            "n_ops": f.n_ops,
            "convert_ops": f.op_counts.get("stablehlo.convert", 0),
            "donated": list(f.declared_donate),
            "aliased": list(f.aliased),
            "transfers": list(f.transfers),
            "op_counts": f.op_counts,
        }
        if f.cost is not None:
            programs[f.name]["cost"] = f.cost.manifest_entry()
    return {"jax": jax.__version__, "programs": programs}


def load_manifest(path) -> dict | None:
    try:
        return json.loads(Path(path).read_text(encoding="utf-8"))
    except (OSError, json.JSONDecodeError):
        return None


def write_manifest(path, manifest: dict) -> None:
    Path(path).write_text(json.dumps(manifest, indent=2) + "\n",
                          encoding="utf-8")


def _cost_drift(old_cost: dict, new_cost: dict) -> list:
    """Per-field relative drifts beyond COST_DRIFT_TOLERANCE, as
    rendered fragments ("hbm_bytes 1.2e6 -> 2.6e6 (+117%)")."""
    frags = []
    for key in ("flops", "hbm_bytes", "scan_depth", "peak_live_bytes",
                "ici_bytes"):
        a, b = old_cost.get(key), new_cost.get(key)
        if a is None or b is None or a == b:
            continue
        base = max(abs(a), 1)
        rel = (b - a) / base
        if abs(rel) > COST_DRIFT_TOLERANCE:
            frags.append(f"{key} {a:g} -> {b:g} ({rel:+.0%})")
    return frags


def diff_manifest(old: dict | None, new: dict, skipped=()) -> list:
    """Human-readable drift lines between the checked-in manifest and
    the freshly lowered one (empty = no drift). Programs named in
    ``skipped`` (not lowerable in this environment) are ignored;
    everything else — fingerprint changes, op-count deltas,
    added/removed programs — is drift. A JAX version change is reported
    as one actionable line instead of a wall of per-program fingerprint
    noise: the lowered text is version-specific by construction.

    Modeled-cost drift gets the same one-actionable-line treatment: a
    program whose cost fingerprint (flops / HBM bytes / scan depth /
    peak live bytes) moved beyond COST_DRIFT_TOLERANCE is reported as
    *what got more expensive and by how much* — the perf-regression
    gate that works without a bench run — instead of (or ahead of) the
    raw op-count delta."""
    if old is None:
        return [f"no checked-in manifest: {len(new['programs'])} "
                "program(s) unaccounted — regenerate with "
                "--write-manifest and commit it"]
    if old.get("jax") != new.get("jax"):
        return [f"manifest was generated under jax {old.get('jax')} but "
                f"this environment runs jax {new.get('jax')} — lowered "
                "programs are version-specific; regenerate with "
                "--write-manifest under the CI jax version and review "
                "the op-count deltas in the diff"]
    lines = []
    olds, news = old.get("programs", {}), new["programs"]
    for name in sorted(set(olds) - set(news) - set(skipped)):
        lines.append(f"{name}: in the manifest but no longer lowered "
                     "(registry entry removed?)")
    for name in sorted(set(news) - set(olds)):
        lines.append(f"{name}: lowered but absent from the manifest "
                     "(new program — regenerate the manifest)")
    for name in sorted(set(news) & set(olds)):
        o, n = olds[name], news[name]
        cost_frags = _cost_drift(o.get("cost", {}), n.get("cost", {}))
        if cost_frags:
            # The actionable line: what got more expensive, by how
            # much, against the tolerance — one line per program.
            lines.append(
                f"{name}: modeled cost drifted beyond "
                f"{COST_DRIFT_TOLERANCE:.0%} ({'; '.join(cost_frags)})"
                " — a perf-relevant compiled-program change; if "
                "intentional, regenerate with --write-manifest and "
                "justify the new cost in review")
            continue
        if o.get("fingerprint") == n["fingerprint"]:
            continue
        deltas = []
        oc, nc = o.get("op_counts", {}), n["op_counts"]
        for op in sorted(set(oc) | set(nc)):
            a, b = oc.get(op, 0), nc.get(op, 0)
            if a != b:
                deltas.append(f"{op} {a}->{b}")
        detail = ("; ".join(deltas[:8]) if deltas
                  else "same op counts, different structure")
        lines.append(f"{name}: compiled program drifted "
                     f"({o.get('n_ops')} -> {n['n_ops']} ops: {detail}"
                     "; modeled cost within tolerance)")
    return lines


# --- d2h whitelist validation --------------------------------------------

_TRANSFER_FUNCS = {"device_get", "asarray", "array", "copy_to_host"}


def _calls_in(fnode: ast.AST):
    for node in ast.walk(fnode):
        if isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Attribute):
                yield f.attr
            elif isinstance(f, ast.Name):
                yield f.id


def validate_d2h_whitelist(project) -> list:
    """Cross-check rules_jax.D2H_SANCTIONED against the code: every
    sanctioned name must still *perform* a device->host transfer
    (jax.device_get / np.asarray of a device value) or delegate to
    another sanctioned name. The audited programs contain no in-flight
    transfers (see check_program), so these seams are, verifiably, the
    only places bytes cross — an entry that stopped transferring is a
    stale hole in the d2h fence."""
    from .rules_jax import D2H_SANCTIONED, D2H_SCOPES

    defs: dict = {}
    for mod in project.modules:
        parts = mod.relpath.split("/")
        if not any(p in parts for p in D2H_SCOPES):
            continue
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name in D2H_SANCTIONED:
                defs.setdefault(node.name, []).append((mod, node))

    findings = []
    for name in sorted(D2H_SANCTIONED):
        sites = defs.get(name)
        if not sites:
            findings.append(Finding(
                STALE_D2H, "bucketeer_tpu/analysis/rules_jax.py", 1,
                f"d2h whitelist entry '{name}' matches no function in "
                "the codec/parallel layers — remove it from "
                "D2H_SANCTIONED", WARNING))
            continue
        for mod, node in sites:
            called = set(_calls_in(node))
            if called & _TRANSFER_FUNCS or called & (D2H_SANCTIONED
                                                    - {name}):
                continue
            findings.append(Finding(
                STALE_D2H, mod.relpath, node.lineno,
                f"d2h whitelist entry '{name}' no longer performs a "
                "device->host transfer (no jax.device_get / np.asarray "
                "and no call into another sanctioned seam) — stale "
                "whitelist entries widen the fence for free",
                WARNING, mod.source_line(node.lineno)))
    return findings


# --- the full audit ------------------------------------------------------

def run_audit(manifest_path, package_root=None, dump_dir=None,
              facts=None):
    """Lower + verify every registered program, validate the d2h
    whitelist, and diff the manifest. Returns (findings, manifest,
    facts). ``facts`` accepts a precomputed ``run_programs()`` result
    so a CLI run combining ``--audit`` with ``--cost`` lowers the
    registry once. On any program-level failure with ``dump_dir`` set,
    the lowered text of every program is written there for the CI
    artifact upload."""
    from .lint import load_project

    all_facts = run_programs() if facts is None else facts
    findings = []
    for facts in all_facts:
        findings += check_program(facts)
    lowered = [f for f in all_facts if not f.skipped]
    if len(lowered) < 3:
        findings.append(Finding(
            MANIFEST_DRIFT, "<deviceaudit>", 0,
            f"only {len(lowered)} program(s) lowered — the audit "
            "needs the registry to cover the jitted entry points "
            f"(skipped: {[f.name for f in all_facts if f.skipped]})",
            ERROR))
    manifest = manifest_from_facts(all_facts)
    for line in diff_manifest(
            load_manifest(manifest_path), manifest,
            skipped=tuple(f.name for f in all_facts if f.skipped)):
        findings.append(Finding(MANIFEST_DRIFT, str(manifest_path), 0,
                                line, ERROR))
    if package_root is not None:
        findings += validate_d2h_whitelist(load_project(Path(package_root)))
    if findings and dump_dir:
        dump = Path(dump_dir)
        dump.mkdir(parents=True, exist_ok=True)
        for facts in all_facts:
            if facts.text:
                safe = re.sub(r"[^\w.\-]", "_", facts.name)
                (dump / f"{safe}.stablehlo.txt").write_text(
                    facts.text, encoding="utf-8")
    return findings, manifest, all_facts
