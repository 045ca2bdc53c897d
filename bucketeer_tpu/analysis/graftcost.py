"""graftcost: static roofline & memory-traffic audit of lowered programs.

deviceaudit (PR 7/9) already lowers every registered jitted entry point
to StableHLO for *correctness* facts — donation aliasing, host
round-trips, f64. The same artifacts carry everything needed for a
static *performance* model, so this module walks the lowered text and
reports, per program × bucket:

- **FLOPs and HBM bytes moved**, op by op. Bytes follow a fusion-region
  model: maximal producer→consumer chains of elementwise/layout ops
  count as one kernel whose intermediates never touch HBM; anchors
  (``dot_general``, ``reduce``, ``gather``/``scatter``, ``concatenate``,
  dynamic slicing, ``while``, calls) are materialization boundaries. A
  value crossing a boundary is charged one write plus one read per
  consuming region — the zero-work accounting style the Sparse Tensor
  Format Conversion literature uses to justify layout changes without a
  benchmark run.
- **Arithmetic intensity and a roofline classification** against a
  pluggable :class:`MachineModel` (``cpu`` and a TPU-v4-like default):
  modeled time = max(flops/peak, bytes/bw) + sequential-step overhead;
  bound = whichever term dominates.
- **Sequential-scan depth**: total ``stablehlo.while`` trips on the
  critical path (nested loops multiply). This quantifies the
  per-symbol CX/D+MQ scans — the ROADMAP's "62 s elephant" — and makes
  "stripe-column vectorization cut trip count 4×" a statically
  checkable claim: the manifest drift gate fails when it moves.
- **Peak live-buffer estimate** (linear-scan SSA liveness, per body)
  against the machine's VMEM budget — whether an ideal Pallas kernel
  could keep the working set resident.

Model caveats, on the record: fusion here is a *model* of what XLA
does, not a readout of what it did (the audit lowers pre-optimization
StableHLO); ``while`` carries are charged at the materialization
boundary every trip, which a VMEM-resident Pallas kernel genuinely
avoids — that conservatism is what makes the per-symbol scans score as
catastrophically memory-bound, which is the point. Machine numbers are
order-of-magnitude; :func:`tier1_prediction` states the model's
device-Tier-1 symbol rate so a chip measurement of the fused program
can calibrate it — it is not to be trusted uncalibrated.

The module also owns the **workload-shape histogram**: the codec's
pow-2 bucket seams (``frontend.dispatch_frontend``, ``cxd.run_cxd`` /
``run_device_mq``, ``decode.device.run_inverse``,
``pipeline.run_tiles``) record (real, padded) pairs through
:func:`record_bucket` — a module-global no-op-priced seam, like
``retrace`` — and :func:`padding_waste` turns a recorded histogram
into the fraction of modeled compute spent on bucket padding, per
bucket and overall.

Findings over these facts live in :mod:`rules_perf`; the CLI surface
is ``python -m bucketeer_tpu.analysis --cost [--machine tpu_v4|cpu]
[--cost-report out.json]``.
"""
from __future__ import annotations

import re
import threading
from dataclasses import dataclass, field

# --- machine models ------------------------------------------------------

@dataclass(frozen=True)
class MachineModel:
    """Roofline parameters for one execution target.

    Numbers are deliberately order-of-magnitude — the model ranks
    programs and detects drift; it does not promise wall clock.
    ``seq_step_s`` is the per-iteration overhead of a sequential
    ``while`` trip (loop dispatch/sync), the term that dominates
    per-symbol scans; ``vmem_bytes`` is the fast-memory budget a
    resident kernel must fit (TPU VMEM ~16 MB/core per the Pallas
    guide; the CPU entry uses a last-level-cache proxy).

    ``ici_bandwidth`` (bytes/s, per device) and ``n_devices`` extend
    the roofline to sharded programs (analysis/graftmesh.py): modeled
    time becomes max(compute, HBM, ICI) where the ICI term is the
    ring-model bytes each device moves over its links per launch. The
    ``cpu`` entry models the forced 8-device host mesh whose "links"
    are shared-memory copies — near-zero-cost, so a CPU mesh audit
    ranks compute/HBM exactly like the single-device one while still
    pricing the collectives it finds."""
    name: str
    peak_flops: float        # sustained vector flop/s (not MXU bf16)
    hbm_bytes_per_s: float
    vmem_bytes: int
    seq_step_s: float
    ici_bandwidth: float = 0.0   # per-device link bytes/s; 0 = no mesh
    n_devices: int = 1           # devices in the modeled mesh

    def ridge(self) -> float:
        """Arithmetic intensity (flop/byte) where the roofline bends."""
        return self.peak_flops / self.hbm_bytes_per_s


MACHINES = {
    "tpu_v4": MachineModel("tpu_v4", peak_flops=4.0e12,
                           hbm_bytes_per_s=1.2e12,
                           vmem_bytes=16 * 1024 * 1024,
                           seq_step_s=1.0e-6,
                           # ~ring bandwidth per chip over the 3D-torus
                           # ICI links; one v4 host = 4 chips.
                           ici_bandwidth=9.0e10, n_devices=4),
    # TPU v5e (device_kind "TPU v5 lite"). Published, Google Cloud
    # "TPU v5e" page: 819 GB/s of HBM bandwidth (16 GB of HBM) and
    # 1,600 Gbit/s of ICI per chip; one v5e host here = 4 chips (2x2).
    # Estimates, not published: the vector (non-MXU) flop/s — only the
    # 197 TFLOP/s bf16 MXU peak is — the 16 MiB VMEM budget (the
    # compiler's default scoped-VMEM limit) and the per-trip overhead.
    "tpu_v5e": MachineModel("tpu_v5e", peak_flops=4.0e12,
                            hbm_bytes_per_s=819e9,
                            vmem_bytes=16 * 1024 * 1024,
                            seq_step_s=1.0e-6,
                            ici_bandwidth=1600e9 / 8, n_devices=4),
    "cpu": MachineModel("cpu", peak_flops=1.0e11,
                        hbm_bytes_per_s=3.0e10,
                        vmem_bytes=32 * 1024 * 1024,
                        seq_step_s=5.0e-6,
                        # The forced host mesh: "links" are memcpys
                        # through shared memory, effectively free next
                        # to the compute/HBM terms.
                        ici_bandwidth=1.0e12, n_devices=8),
}
DEFAULT_MACHINE = "tpu_v4"

# --- StableHLO types ------------------------------------------------------

_DTYPE_BYTES = {"i1": 1, "i2": 1, "i4": 1, "i8": 1, "ui8": 1,
                "i16": 2, "ui16": 2, "f16": 2, "bf16": 2,
                "i32": 4, "ui32": 4, "f32": 4,
                "i64": 8, "ui64": 8, "f64": 8, "c64": 8, "c128": 16}


@dataclass(frozen=True)
class TType:
    """One ``tensor<...>`` type: static shape + element width."""
    shape: tuple
    dtype: str

    @property
    def elems(self) -> int:
        n = 1
        for d in self.shape:
            n *= d
        return n

    @property
    def nbytes(self) -> int:
        return self.elems * _DTYPE_BYTES.get(self.dtype, 4)


_TENSOR_RE = re.compile(r"tensor<([^>]*)>")


def parse_type(text: str) -> TType | None:
    """``tensor<7x64x64xi32>`` -> TType((7, 64, 64), "i32");
    ``tensor<f32>`` -> scalar. None when no tensor type is present."""
    m = _TENSOR_RE.search(text)
    if not m:
        return None
    parts = m.group(1).split("x")
    dims = []
    for p in parts[:-1]:
        dims.append(int(p) if p.isdigit() else 1)   # "?" -> 1
    return TType(tuple(dims), parts[-1])


def _parse_type_list(text: str) -> list:
    return [parse_type("tensor<" + g + ">")
            for g in _TENSOR_RE.findall(text)]


# --- StableHLO text parsing ----------------------------------------------

@dataclass
class HloOp:
    """One parsed op. ``regions`` holds nested op lists — only control
    flow (``while`` cond/do) is kept; combinator regions (reduce /
    scatter update computations) are skipped at parse time and their
    cost folded into the op itself. A body's terminator is kept as a
    pseudo-op named ``return`` so fused values escaping through it get
    their materialization write."""
    result: str              # base SSA name ("%6" for "%6:3")
    name: str                # "stablehlo.while"
    operands: tuple          # SSA refs as written (may carry "#k")
    rtypes: tuple            # result TTypes
    attrs: str               # raw op text (for contracting_dims etc.)
    regions: list = field(default_factory=list)


@dataclass
class HloFunc:
    name: str
    args: list               # [(name, TType)]
    results: list            # [TType]
    body: list               # [HloOp]


_FUNC_RE = re.compile(r"^\s*func\.func\s+(?:public\s+|private\s+)?"
                      r"@(\w+)\((.*?)\)\s*->\s*(.*?)(?:attributes .*)?"
                      r"\s*\{\s*$")
_ARG_RE = re.compile(r"(%\w+):\s*(tensor<[^>]*>)")
_OP_RE = re.compile(r"^\s*(%[\w]+(?::\d+)?)\s*=\s*\"?([a-z_]+[\w.]*)\"?"
                    r"\s*(.*)$", re.DOTALL)
_RETURN_RE = re.compile(r"^\s*(?:stablehlo\.|func\.)?return\b(.*)$")
_REF_RE = re.compile(r"%[\w]+(?:#\d+)?")
_ITER_RE = re.compile(r"(%\w+)\s*=\s*(%\w+(?:#\d+)?)")
_DENSE_INT_RE = re.compile(r"dense<(-?\d+)>")


def _split_types(rest: str):
    """(head, types, is_fn_type) from an op line's tail. The type
    annotation is everything after the last top-level `` : ``; the
    function-typed form ``(a, b) -> c`` yields the result types after
    the arrow, the plain form yields the listed types verbatim."""
    idx = rest.rfind(" : ")
    if idx < 0:
        return rest, [], False
    head, tail = rest[:idx], rest[idx + 3:].strip()
    if tail.startswith("("):
        arrow = tail.rfind("->")
        return head, _parse_type_list(tail[arrow + 2:]
                                      if arrow >= 0 else tail), True
    return head, _parse_type_list(tail), False


def _operand_refs(head: str) -> tuple:
    """SSA refs in an op's pre-type text, order-stable, deduplicated,
    keeping any ``#k`` component selector."""
    out, seen = [], set()
    for m in _REF_RE.finditer(head):
        if m.group(0) not in seen:
            seen.add(m.group(0))
            out.append(m.group(0))
    return tuple(out)


def parse_module(text: str) -> dict:
    """Lowered StableHLO text -> {function name: HloFunc}.

    Line-oriented with a region stack: ``while`` ops open ``cond {`` /
    ``} do {`` regions that are parsed recursively; combinator regions
    opened with ``({`` (scatter update computations, sort comparators)
    are skipped to their closing ``})`` line, which also carries the
    op's type annotation."""
    funcs: dict = {}
    lines = text.splitlines()
    i, n = 0, len(lines)
    cur_func = None
    stack: list = []         # [(op list, pending while op or None)]

    while i < n:
        line = lines[i]
        stripped = line.strip()
        m = _FUNC_RE.match(line)
        if m:
            cur_func = HloFunc(
                m.group(1),
                [(a, parse_type(t)) for a, t in _ARG_RE.findall(m.group(2))],
                _parse_type_list(m.group(3)), [])
            funcs[cur_func.name] = cur_func
            stack = [(cur_func.body, None)]
            i += 1
            continue
        if cur_func is None:
            i += 1
            continue
        if stripped.startswith("cond {") or stripped.startswith("} do {"):
            if stripped.startswith("} do {"):
                stack.pop()
            op = stack[-1][1]
            op.regions.append([])
            stack.append((op.regions[-1], None))
            i += 1
            continue
        if stripped == "}":
            if len(stack) > 1:
                stack.pop()
                stack[-1] = (stack[-1][0], None)   # while complete
            else:
                cur_func = None
            i += 1
            continue
        rm = _RETURN_RE.match(line)
        if rm:
            head, _, _ = _split_types(rm.group(1))
            stack[-1][0].append(HloOp("", "return",
                                      _operand_refs(head), (), stripped))
            i += 1
            continue
        om = _OP_RE.match(line)
        if om:
            result, opname, rest = om.groups()
            if "({" in rest:
                # Combinator region: skip to the closing "})" line and
                # splice its type annotation onto the op text.
                depth = rest.count("{") - rest.count("}")
                while depth > 0 and i + 1 < n:
                    i += 1
                    depth += lines[i].count("{") - lines[i].count("}")
                rest = rest + " " + lines[i].strip()
            head, types, is_fn = _split_types(rest)
            types = [t for t in types if t is not None]
            if opname == "stablehlo.while" or is_fn:
                rtypes = tuple(types)
            else:
                rtypes = tuple(types[-1:])
            op = HloOp(result.split(":")[0], opname,
                       _operand_refs(head), rtypes, rest)
            stack[-1][0].append(op)
            if opname == "stablehlo.while":
                stack[-1] = (stack[-1][0], op)
        i += 1
    return funcs


# --- the op-walk cost model ----------------------------------------------

# Ops XLA fuses into their consumers: elementwise arithmetic plus
# layout/generator ops whose output never needs to exist in HBM when
# every consumer sits in the same kernel.
_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "remainder", "power",
    "negate", "abs", "sign", "and", "or", "xor", "not", "compare",
    "select", "clamp", "minimum", "maximum", "shift_left",
    "shift_right_arithmetic", "shift_right_logical", "convert",
    "floor", "ceiling", "round_nearest_even", "round_nearest_afz",
    "exponential", "exponential_minus_one", "log", "log_plus_one",
    "tanh", "logistic", "sqrt", "rsqrt", "cosine", "sine", "is_finite",
    "popcnt", "count_leading_zeros",
}
_LAYOUT = {"reshape", "transpose", "broadcast_in_dim", "slice",
           "reverse", "pad", "iota", "constant", "bitcast_convert"}
_FUSIBLE = _ELEMENTWISE | _LAYOUT

# Per-element flop weights; layout/movement ops cost 0 flops.
_FLOP_WEIGHT = {"divide": 4, "remainder": 4, "power": 8,
                "exponential": 8, "exponential_minus_one": 8, "log": 8,
                "log_plus_one": 8, "tanh": 8, "logistic": 8, "sqrt": 4,
                "rsqrt": 4, "cosine": 8, "sine": 8, "clamp": 2}

_CONTRACT_RE = re.compile(r"contracting_dims\s*=\s*\[([\d, ]*)\]")


def _short(name: str) -> str:
    return name.split(".", 1)[1] if "." in name else name


def _fn_operand_types(op: HloOp) -> list:
    """Operand types from a function-typed annotation ``(a, b) -> c``
    (everything before the arrow), or [] for plain-typed ops."""
    idx = op.attrs.rfind(" : ")
    if idx < 0:
        return []
    tail = op.attrs[idx + 3:].strip()
    if not tail.startswith("("):
        return []
    arrow = tail.rfind("->")
    return [t for t in _parse_type_list(tail[:arrow if arrow >= 0
                                             else len(tail)]) if t]


def _op_flops(op: HloOp) -> int:
    short = _short(op.name)
    out = op.rtypes[0] if op.rtypes else None
    if short == "dot_general":
        ins = _fn_operand_types(op)
        k = 1
        m = _CONTRACT_RE.search(op.attrs)
        if m and m.group(1).strip() and ins:
            lhs = ins[0]
            for d in m.group(1).split(","):
                d = int(d.strip())
                if d < len(lhs.shape):
                    k *= lhs.shape[d]
        return 2 * (out.elems if out else 0) * k
    if short == "reduce":
        ins = _fn_operand_types(op)
        return ins[0].elems if ins else 0
    if short == "scatter":
        ins = _fn_operand_types(op)
        # (operand, indices, updates) -> out: one combinator
        # application per update element.
        return ins[2].elems if len(ins) >= 3 else 0
    if short in _ELEMENTWISE:
        return (out.elems if out else 0) * _FLOP_WEIGHT.get(short, 1)
    return 0


@dataclass
class Cost:
    """Accumulated model for one body/program."""
    flops: int = 0
    hbm_bytes: int = 0
    scan_depth: int = 0       # sequential trips, nested multiplied
    max_trip: int = 0         # largest single while trip count
    n_whiles: int = 0
    unknown_trips: int = 0    # whiles whose trip count was unreadable
    peak_live_bytes: int = 0

    def add(self, other: "Cost", times: int = 1) -> None:
        self.flops += other.flops * times
        self.hbm_bytes += other.hbm_bytes * times
        self.scan_depth += other.scan_depth * times
        self.max_trip = max(self.max_trip, other.max_trip)
        self.n_whiles += other.n_whiles
        self.unknown_trips += other.unknown_trips
        self.peak_live_bytes = max(self.peak_live_bytes,
                                   other.peak_live_bytes)


def _while_trips(op: HloOp, consts: dict) -> int | None:
    """Trip count from the cond region: the loop counter is compared
    against a scalar integer constant (the ``lax.scan``/``fori_loop``
    lowering). None when unreadable."""
    if not op.regions:
        return None
    local = dict(consts)
    for c in op.regions[0]:
        if _short(c.name) == "constant":
            m = _DENSE_INT_RE.search(c.attrs)
            if m:
                local[c.result] = int(m.group(1))
    for c in op.regions[0]:
        if _short(c.name) == "compare":
            for ref in c.operands:
                v = local.get(ref.split("#")[0])
                if v is not None and v > 0:
                    return v
    return None


def _body_cost(body: list, env: dict, func_costs: dict,
               consts: dict) -> Cost:
    """Model one straight-line op list.

    ``env`` maps externally visible SSA names (function args, while
    carries, captured outer values) to tuples of TTypes; ``consts``
    carries scalar integer constants visible from enclosing scopes
    (trip-count extraction)."""
    cost = Cost()
    types: dict = dict(env)        # base name -> tuple(TType)
    producer: dict = {}            # base name -> op index
    fusible: dict = {}             # op index -> bool
    parent: dict = {}              # union-find over fusible op indices

    def find(x):
        while parent.get(x, x) != x:
            parent[x] = parent.get(parent[x], parent[x])
            x = parent[x]
        return x

    def typeof(ref):
        base, _, k = ref.partition("#")
        t = types.get(base)
        if not t:
            return None
        if k:
            ki = int(k)
            return t[ki] if ki < len(t) else None
        return t[0]

    # Pass 1: classify, union producer->consumer chains of fusible
    # ops, collect scalar constants.
    for idx, op in enumerate(body):
        parent[idx] = idx
        short = _short(op.name)
        if short == "constant":
            m = _DENSE_INT_RE.search(op.attrs)
            if m:
                consts[op.result] = int(m.group(1))
        fus = (op.name.startswith("stablehlo.") and short in _FUSIBLE)
        fusible[idx] = fus
        if op.result:
            producer[op.result] = idx
            types[op.result] = op.rtypes
        if fus:
            for ref in op.operands:
                p = producer.get(ref.split("#")[0])
                if p is not None and fusible.get(p):
                    parent[find(idx)] = find(p)

    # Pass 2: flops + boundary traffic.
    reads: dict = {}               # (region, ref) -> bytes
    escapes: set = set()           # fused values needing a write

    def mark_escape(ref):
        """A fused value crossing a boundary materializes — except
        constants: immutable program data is only ever read, never
        written back."""
        base = ref.split("#")[0]
        p = producer.get(base)
        if p is not None and fusible.get(p) \
                and _short(body[p].name) != "constant":
            escapes.add(base)

    for idx, op in enumerate(body):
        short = _short(op.name)
        region = find(idx)
        cost.flops += _op_flops(op)
        if short == "return":
            for ref in op.operands:
                mark_escape(ref)
            continue
        if not fusible[idx]:
            # Any fused value entering an anchor (or a loop/callee)
            # materializes first: charge its write exactly once, here,
            # to match the documented one-write-plus-one-read-per-
            # consuming-region boundary accounting.
            for ref in op.operands:
                mark_escape(ref)
        if short == "while":
            trips = _while_trips(op, consts)
            if trips is None:
                trips = 1
                cost.unknown_trips += 1
            cost.n_whiles += 1
            # Carry regions see the enclosing scope (captures) plus
            # the %iterArg names bound positionally to the carry types.
            carry_env = dict(types)
            iter_names = [nm for nm, _ in _ITER_RE.findall(op.attrs)
                          if nm.startswith("%iterArg")]
            for pos, nm in enumerate(iter_names):
                if pos < len(op.rtypes):
                    carry_env[nm] = (op.rtypes[pos],)
            inner = Cost()
            for reg in op.regions:
                inner.add(_body_cost(reg, carry_env, func_costs,
                                     dict(consts)))
            cost.flops += inner.flops * trips
            cost.hbm_bytes += inner.hbm_bytes * trips
            cost.scan_depth += trips * max(1, inner.scan_depth)
            cost.max_trip = max(cost.max_trip, trips, inner.max_trip)
            cost.n_whiles += inner.n_whiles
            cost.unknown_trips += inner.unknown_trips
            cost.peak_live_bytes = max(
                cost.peak_live_bytes,
                inner.peak_live_bytes
                + sum(t.nbytes for t in op.rtypes))
            # Carry init read + final write, once each.
            carry_bytes = sum(t.nbytes for t in op.rtypes)
            cost.hbm_bytes += 2 * carry_bytes
            continue
        if short == "call":
            callee = re.search(r"@(\w+)", op.attrs)
            sub = func_costs.get(callee.group(1)) if callee else None
            if sub is not None:
                cost.add(sub)
            continue
        if not fusible[idx]:
            # Anchor: charge surgical traffic at the op.
            out_b = sum(t.nbytes for t in op.rtypes)
            ins = _fn_operand_types(op)
            if short == "dynamic_slice":
                cost.hbm_bytes += 2 * out_b
            elif short == "dynamic_update_slice":
                upd = ins[1].nbytes if len(ins) >= 2 else out_b
                cost.hbm_bytes += 2 * upd
            elif short == "gather":
                idx_b = ins[1].nbytes if len(ins) >= 2 else 0
                cost.hbm_bytes += 2 * out_b + idx_b
            elif short == "scatter":
                upd = (sum(t.nbytes for t in ins[1:])
                       if len(ins) >= 3 else out_b)
                cost.hbm_bytes += 2 * upd
            else:
                r = 0
                for ref in op.operands:
                    t = typeof(ref)
                    if t is not None:
                        r += t.nbytes
                cost.hbm_bytes += r + out_b
            continue
        # Fusible op: charge reads of values produced outside its
        # fused region (anchor outputs, args, captures, constants from
        # other regions), once per (region, value).
        for ref in op.operands:
            base = ref.split("#")[0]
            p = producer.get(base)
            if p is not None and fusible.get(p):
                if find(p) != region:
                    mark_escape(ref)
                    t = typeof(ref)
                    if t is not None:
                        reads[(region, ref)] = t.nbytes
                continue
            t = typeof(ref)
            if t is not None:
                reads[(region, ref)] = t.nbytes
    cost.hbm_bytes += sum(reads.values())
    for base in escapes:
        t = types.get(base)
        if t:
            cost.hbm_bytes += t[0].nbytes

    # Peak live bytes: linear-scan SSA liveness over this body; region
    # args count only when actually referenced.
    referenced = {ref.split("#")[0] for op in body
                  for ref in op.operands}
    live = sum(t[0].nbytes for name, t in env.items()
               if name in referenced and t)
    peak = live
    last_use: dict = {}
    for idx, op in enumerate(body):
        for ref in op.operands:
            base = ref.split("#")[0]
            if base in producer:
                last_use[base] = idx
    expiry: dict = {}
    for base, idx in last_use.items():
        t = types.get(base)
        if t:
            expiry.setdefault(idx, []).append(
                sum(x.nbytes for x in t))
    for idx, op in enumerate(body):
        if op.result and op.rtypes:
            live += sum(t.nbytes for t in op.rtypes)
        peak = max(peak, live)
        for b in expiry.get(idx, ()):
            live -= b
    cost.peak_live_bytes = max(cost.peak_live_bytes, peak)
    return cost


@dataclass
class CostFacts:
    """The modeled cost of one lowered program."""
    name: str
    flops: int = 0
    hbm_bytes: int = 0
    scan_depth: int = 0
    max_trip: int = 0
    n_whiles: int = 0
    unknown_trips: int = 0
    peak_live_bytes: int = 0
    input_bytes: int = 0
    output_bytes: int = 0
    output_sizes: tuple = ()       # per-result bytes of ``main``
    ici_bytes: int = 0             # per-device ring-model link bytes
                                   # (graftmesh sets this from the
                                   # partitioned HLO's collectives)

    @property
    def intensity(self) -> float:
        return self.flops / self.hbm_bytes if self.hbm_bytes else 0.0

    def roofline(self, machine: MachineModel) -> dict:
        t_compute = self.flops / machine.peak_flops
        t_memory = self.hbm_bytes / machine.hbm_bytes_per_s
        t_ici = (self.ici_bytes / machine.ici_bandwidth
                 if machine.ici_bandwidth else 0.0)
        t_seq = self.scan_depth * machine.seq_step_s
        if t_seq > max(t_compute, t_memory, t_ici):
            bound = "sequential"
        elif t_ici > max(t_compute, t_memory):
            bound = "ici"
        elif t_memory >= t_compute:
            bound = "memory"
        else:
            bound = "compute"
        return {"machine": machine.name,
                "time_s": max(t_compute, t_memory, t_ici) + t_seq,
                "bound": bound,
                "intensity": round(self.intensity, 4),
                "ridge": round(machine.ridge(), 4),
                "fits_vmem": self.peak_live_bytes <= machine.vmem_bytes}

    def manifest_entry(self) -> dict:
        """The cost fingerprint joining ``.graftaudit-manifest.json``
        (deviceaudit.manifest_from_facts). A pure function of the
        lowered text — reproducible from any entry point."""
        entry = {"flops": self.flops, "hbm_bytes": self.hbm_bytes,
                 "scan_depth": self.scan_depth,
                 "max_trip": self.max_trip,
                 "peak_live_bytes": self.peak_live_bytes,
                 "intensity": round(self.intensity, 4)}
        if self.ici_bytes:
            # Only sharded programs carry interconnect traffic; keeping
            # the key off single-device entries leaves the checked-in
            # manifest byte-stable for them.
            entry["ici_bytes"] = self.ici_bytes
        return entry


def cost_program(text: str, name: str = "<program>") -> CostFacts:
    """Model one lowered program's ``main`` (private helpers inlined at
    their call sites; while bodies multiplied by extracted trips)."""
    funcs = parse_module(text)
    facts = CostFacts(name)
    main = funcs.get("main")
    if main is None:
        return facts
    func_costs: dict = {}
    for fname, fn in funcs.items():
        if fname == "main":
            continue
        env = {a: (t,) for a, t in fn.args if t is not None}
        func_costs[fname] = _body_cost(fn.body, env, func_costs, {})
    env = {a: (t,) for a, t in main.args if t is not None}
    cost = _body_cost(main.body, env, func_costs, {})
    facts.flops = cost.flops
    facts.hbm_bytes = cost.hbm_bytes
    facts.scan_depth = cost.scan_depth
    facts.max_trip = cost.max_trip
    facts.n_whiles = cost.n_whiles
    facts.unknown_trips = cost.unknown_trips
    facts.peak_live_bytes = cost.peak_live_bytes
    facts.input_bytes = sum(t.nbytes for _, t in main.args
                            if t is not None)
    facts.output_sizes = tuple(t.nbytes for t in main.results
                               if t is not None)
    facts.output_bytes = sum(facts.output_sizes)
    return facts


# --- workload-shape histogram (padding waste) ----------------------------

_HIST_LOCK = threading.Lock()
_BUCKET_HIST: dict = {}          # family -> {(real, padded): count}


def record_bucket(family: str, real: int, padded: int) -> None:
    """Record one pow-2 bucket launch: ``real`` live items padded to
    ``padded``. Called from the codec's bucket seams; a dict update
    under a module lock — no device work, no allocation beyond the
    cell."""
    with _HIST_LOCK:
        cells = _BUCKET_HIST.setdefault(family, {})
        key = (int(real), int(padded))
        cells[key] = cells.get(key, 0) + 1


def bucket_histogram() -> dict:
    """Snapshot of the recorded workload-shape histogram."""
    with _HIST_LOCK:
        return {fam: dict(cells) for fam, cells in _BUCKET_HIST.items()}


def reset_histogram() -> None:
    with _HIST_LOCK:
        _BUCKET_HIST.clear()


def padding_waste(hist: dict) -> dict:
    """Fraction of modeled compute spent on pow-2 padding, per family:
    per-bucket occupancy plus the launch-weighted overall waste
    (1 - sum(real)/sum(padded)). Static bucket shapes mean a padded
    item costs exactly what a live item costs — waste is linear in the
    count."""
    out = {}
    for family, cells in hist.items():
        buckets: dict = {}
        real_sum = padded_sum = launches = 0
        for (real, padded), count in cells.items():
            b = buckets.setdefault(padded, {"real": 0, "padded": 0,
                                            "launches": 0})
            b["real"] += real * count
            b["padded"] += padded * count
            b["launches"] += count
            real_sum += real * count
            padded_sum += padded * count
            launches += count
        for b in buckets.values():
            b["waste"] = (round(1.0 - b["real"] / b["padded"], 4)
                          if b["padded"] else 0.0)
        out[family] = {
            "launches": launches,
            "waste": (round(1.0 - real_sum / padded_sum, 4)
                      if padded_sum else 0.0),
            "buckets": {str(k): v for k, v in sorted(buckets.items())},
        }
    return out


# --- report assembly ------------------------------------------------------

def cost_report(all_facts: list, machine: MachineModel,
                hist: dict | None = None) -> dict:
    """The machine-readable ``--cost-report`` payload: per-program
    modeled cost + roofline for ``machine``, plus padding waste from
    the recorded (or provided) workload-shape histogram."""
    programs = {}
    for f in all_facts:
        if getattr(f, "skipped", ""):
            continue
        c = getattr(f, "cost", f)
        if not isinstance(c, CostFacts):
            continue
        programs[c.name] = dict(c.manifest_entry(),
                                input_bytes=c.input_bytes,
                                output_bytes=c.output_bytes,
                                n_whiles=c.n_whiles,
                                unknown_trips=c.unknown_trips,
                                roofline=c.roofline(machine))
    hist = bucket_histogram() if hist is None else hist
    return {"machine": machine.name, "programs": programs,
            "padding": padding_waste(hist) if hist else {}}


def render_cost_line(c: CostFacts, machine: MachineModel) -> str:
    roof = c.roofline(machine)
    comms = (f"{c.ici_bytes / 1e6:.3g} MB ICI, " if c.ici_bytes
             else "")
    return (f"{c.name}: {c.flops / 1e6:.3g} MFLOP, "
            f"{c.hbm_bytes / 1e6:.3g} MB HBM, {comms}"
            f"intensity {roof['intensity']:.3g} flop/B, "
            f"scan depth {c.scan_depth}, {roof['bound']}-bound "
            f"({machine.name}: {roof['time_s'] * 1e6:.3g} us)")


# --- bench-calibration prediction ----------------------------------------

_PREDICTION_CACHE: dict = {}


# Nominal symbols per modeled block for the calibration metric — the
# historical audit-bucket MQ step count. The fused program's MQ half
# runs a realized-cursor while the static extractor cannot read, so
# its sequential cost is added explicitly below from this count.
PREDICTION_SYMS = 1024


def tier1_prediction() -> dict:
    """Modeled device-Tier-1 symbol throughput per machine model, from
    the registry's fused CX/D+MQ program at its audit bucket (one
    block, L=2). The fused MQ half's trip count is dynamic (realized
    cursor), so the roofline covers the static CX/D scan and the MQ
    sequential term is added as ``PREDICTION_SYMS / MQ_UNROLL`` trips
    of the machine's seq-step overhead. Compare it with a measured
    device-Tier-1 symbol rate to calibrate the machine numbers. Lowers
    one program on first use (cached per process)."""
    if _PREDICTION_CACHE:
        return dict(_PREDICTION_CACHE)
    from . import deviceaudit
    from ..codec.cxd import MQ_UNROLL

    entries = [e for e in deviceaudit.registry()
               if e.name.split("/")[0] == "cxdmq.fused"]
    costs = {}
    for facts in deviceaudit.run_programs(entries):
        if facts.skipped:
            return {}
        # run_programs already attached the modeled cost.
        costs[facts.name.split("/")[0]] = (
            facts.cost or cost_program(facts.text, facts.name))
    fused = costs.get("cxdmq.fused")
    if fused is None:
        return {}
    # Consistency guard (the old code read the count from the modeled
    # MQ bucket; the fused program's MQ length is dynamic, so the
    # workload assumption lives here): the assumed symbol count must
    # fit the registered audit bucket's symbol capacity, read from the
    # registry name — a bucket change that invalidates the assumption
    # trips this instead of silently skewing the calibration metric.
    from ..codec.cxd import max_syms
    m = re.search(r"/L(\d+)/", fused.name)
    if m is None or PREDICTION_SYMS > max_syms(int(m.group(1))):
        return {}
    syms = float(PREDICTION_SYMS)
    mq_trips = -(-PREDICTION_SYMS // MQ_UNROLL)
    out = {}
    for mname, machine in MACHINES.items():
        t = (fused.roofline(machine)["time_s"]
             + mq_trips * machine.seq_step_s)
        out[mname] = {"symbols_per_s": round(syms / t, 1),
                      "modeled_block_s": round(t, 6)}
    _PREDICTION_CACHE.update(out)
    return dict(out)
