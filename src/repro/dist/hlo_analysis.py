"""Post-compile HLO accounting: collectives, dot FLOPs, HBM traffic.

``analyze_hlo(compiled.as_text())`` parses the optimized HLO module text —
no XLA internals, just the stable text format — and returns per-kind
collective counts/bytes plus dot-FLOP and memory-traffic estimates. The
launch dry-run records these per (arch × shape × mesh) cell, and the
sharded train path uses :func:`count_axis_crossing` to assert the FedFog
round contains exactly the paper's ONE inter-client all-reduce.

Collectives inside while-loop bodies are counted ONCE (static texts carry
no trip counts); such ops are surfaced in ``trip_count_warnings`` so the
per-round byte totals are read with the right caveat.

The same pass maps each instruction to the innermost ``fedfog.*`` scope
(``jax.named_scope``) in its ``metadata={op_name=...}``: ``phases``. An
instruction that the compiler made without metadata (copies, converts,
async starts and dones, wrapped ops) takes, in this order, the scope of
the computation it calls (its root's, else its first scoped
instruction's), of its first scoped operand, or of the instruction that
calls its own computation (a ``while`` body from its ``while``). XLA keeps
scopes in metadata only, and JAX's persistent cache leaves metadata out
of its key unless told otherwise, so an executable loaded from a cache
entry written by unscoped code maps nothing.
"""
from __future__ import annotations

import dataclasses
import math
import re

import numpy as np

# Bytes per element for HLO primitive types.
_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
}

# op name (with async -start variants normalized) -> canonical kind
_COLLECTIVE_KINDS = {
    "all-reduce": "all-reduce",
    "all-gather": "all-gather",
    "reduce-scatter": "reduce-scatter",
    "all-to-all": "all-to-all",
    "collective-permute": "collective-permute",
    "collective-broadcast": "collective-broadcast",
    "ragged-all-to-all": "all-to-all",
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# "name = TYPE opcode(": TYPE may be a tuple and carry TPU tiled layouts
# such as ``{3,2,1,0:T(8,128)(2,1)}``; it ends at the first " opcode(".
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s+([\w\-]+)\("
)
_GROUPS_EXPLICIT_RE = re.compile(r"replica_groups=\{(\{[\d,{} ]*\})\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?"
)
_SRC_TGT_RE = re.compile(r"source_target_pairs=\{([\d,{} ]*)\}")
# Greedy: the last (innermost) scope of the op_name path.
_PHASE_RE = re.compile(r'op_name="[^"]*(fedfog\.\w+)')
_CALLEE_RE = re.compile(r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_NAME_RE = re.compile(r"%?([\w.\-]+)")
_COMMENT_RE = re.compile(r"/\*.*?\*/")  # e.g. /*index=5*/ in long lists
_MODULE_RE = re.compile(r"^HloModule\s+([\w.\-]+)")


def _shape_bytes(type_str: str) -> float:
    """Total bytes of one HLO result type (sums tuple elements)."""
    total = 0.0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        numel = math.prod(int(d) for d in dims.split(",") if d) if dims else 1
        total += _DTYPE_BYTES[dtype] * numel
    return total


def _shape_dims(type_str: str) -> tuple[int, ...]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return ()
    dims = m.group(2)
    return tuple(int(d) for d in dims.split(",") if d) if dims else ()


def _parse_groups(line: str) -> list[list[int]] | None:
    """Replica groups from either text form; None = no groups attr
    (convention: one group spanning every participant)."""
    m = _GROUPS_EXPLICIT_RE.search(line)
    if m:
        return [
            [int(x) for x in g.split(",") if x.strip()]
            for g in re.findall(r"\{([\d, ]*)\}", m.group(1))
        ]
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        ng, gs = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        ids = np.arange(math.prod(dims)).reshape(dims)
        if m.group(4):
            ids = ids.transpose([int(x) for x in m.group(4).split(",")])
        return ids.reshape(ng, gs).tolist()
    m = _SRC_TGT_RE.search(line)
    if m:  # collective-permute: each pair is a 2-group
        pairs = re.findall(r"\{(\d+),\s*(\d+)\}", m.group(1))
        return [[int(a), int(b)] for a, b in pairs]
    return None


@dataclasses.dataclass(frozen=True)
class CollectiveOp:
    name: str
    kind: str
    bytes: float
    computation: str
    groups: list[list[int]] | None  # None = all participants together
    in_loop_body: bool = False


@dataclasses.dataclass(frozen=True)
class CollectiveStats:
    ops: tuple[CollectiveOp, ...]

    @property
    def count_by_kind(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for op in self.ops:
            out[op.kind] = out.get(op.kind, 0) + 1
        return out

    @property
    def bytes_by_kind(self) -> dict[str, float]:
        out: dict[str, float] = {}
        for op in self.ops:
            out[op.kind] = out.get(op.kind, 0.0) + op.bytes
        return out

    @property
    def total_bytes(self) -> float:
        return sum(op.bytes for op in self.ops)

    @property
    def trip_count_warnings(self) -> list[str]:
        return [
            f"{op.kind} {op.name} ({op.bytes:.2e} B) inside loop body "
            f"{op.computation}: bytes counted once, executes per iteration"
            for op in self.ops
            if op.in_loop_body
        ]


@dataclasses.dataclass(frozen=True)
class HLOAnalysis:
    collectives: CollectiveStats
    dot_flops: float  # 2·M·N·K over every dot (fusion bodies included)
    hbm_bytes: float  # entry args + outputs + materialized fusion results
    hbm_bytes_in: float
    hbm_bytes_out: float
    num_instructions: int
    module: str = ""
    # instruction -> innermost ``fedfog.*`` scope (scoped instructions only)
    phases: dict[str, str] = dataclasses.field(default_factory=dict)
    # instruction -> "TYPE opcode(operand,...)": its result type, opcode
    # and operand names, which a device trace's event name prints too
    # (scoped instructions only)
    heads: dict[str, str] = dataclasses.field(default_factory=dict)


class _PhaseMap:
    """``HLOAnalysis.phases``, built instruction by instruction during the
    one walk over the text, in which a computation's instructions come
    after those of the computations it calls."""

    def __init__(self):
        self.phases: dict[str, str] = {}
        self.operands: dict[str, list[str]] = {}
        self.comp_phase: dict[str, str] = {}  # its ROOT's, else its first
        self.caller: dict[str, str] = {}  # computation -> calling instruction
        self.unscoped: dict[str, list[str]] = {}  # computation -> names

    def add(self, comp: str, name: str, line: str, after_opcode: str):
        callees = _CALLEE_RE.findall(line) + [
            n for g in _BRANCHES_RE.findall(line) for n in _NAME_RE.findall(g)
        ]
        for c in callees:
            self.caller.setdefault(c, name)
        # Compiled text prints operands untyped: the first ")" ends them.
        operands = self.operands[name] = _NAME_RE.findall(
            _COMMENT_RE.sub("", after_opcode.split(")", 1)[0]))
        pm = _PHASE_RE.search(line)
        scope = pm.group(1) if pm else next(
            (self.comp_phase[c] for c in callees if c in self.comp_phase),
            None)
        if scope is None:
            scope = next(
                (self.phases[o] for o in operands if o in self.phases), None)
        if scope is None:
            self.unscoped.setdefault(comp, []).append(name)
            return
        self.phases[name] = scope
        if comp not in self.comp_phase or line.lstrip().startswith("ROOT"):
            self.comp_phase[comp] = scope

    def finish(self) -> dict[str, str]:
        # Callers follow the computations they call: latest first, an
        # outer caller's scope is settled before its callees take it.
        for comp in reversed(list(self.unscoped)):
            scope = self.phases.get(self.caller.get(comp, ""))
            if scope is not None:
                for name in self.unscoped[comp]:
                    self.phases[name] = scope
        return self.phases


def analyze_hlo(hlo_text: str) -> HLOAnalysis:
    """Parse one optimized HLO module's text into traffic/compute stats."""
    shapes: dict[str, str] = {}  # instr name -> type string
    instrs: list[tuple[str, str, str, str, str]] = []  # comp, name, type, op, line
    comp = ""
    loop_bodies: set[str] = set()
    phases = _PhaseMap()
    mm = _MODULE_RE.match(hlo_text)

    for raw in hlo_text.splitlines():
        line = raw.rstrip()
        # Computation header: "%name (params...) -> type {" (or ENTRY ...);
        # no "=" before the parameter list, ends with an opening brace.
        if (
            line.endswith("{")
            and "(" in line
            and "=" not in line.split("(", 1)[0]
        ):
            cm = re.match(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)", line)
            if cm:
                comp = cm.group(1)
                continue
        im = _INSTR_RE.match(line)
        if not im:
            continue
        name, type_str, opcode = im.groups()
        shapes[name] = type_str
        instrs.append((comp, name, type_str, opcode, line))
        phases.add(comp, name, line, line[im.end():])
        if opcode == "while":
            bm = re.search(r"body=%?([\w.\-]+)", line)
            if bm:
                loop_bodies.add(bm.group(1))

    ops: list[CollectiveOp] = []
    dot_flops = 0.0
    entry_params = 0.0
    entry_out = 0.0
    fusion_bytes = 0.0
    entry_comp = instrs[0][0] if instrs else ""
    # The ENTRY computation is the one whose line in the text is marked
    # ENTRY; _COMP_RE can't see the marker after .match groups, so find it
    # directly.
    em = re.search(r"^ENTRY\s+%?([\w.\-]+)", hlo_text, re.M)
    if em:
        entry_comp = em.group(1)

    for comp, name, type_str, opcode, line in instrs:
        base = opcode[:-6] if opcode.endswith("-start") else opcode
        if opcode.endswith("-done"):
            continue  # async pair: counted at -start
        if base in _COLLECTIVE_KINDS:
            ops.append(
                CollectiveOp(
                    name=name,
                    kind=_COLLECTIVE_KINDS[base],
                    bytes=_shape_bytes(type_str),
                    computation=comp,
                    groups=_parse_groups(line),
                    in_loop_body=comp in loop_bodies,
                )
            )
        elif base == "dot":
            dims = _shape_dims(type_str)
            cm = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", line)
            # First operand: "dot(f32[8,16]{1,0} %arg0, ..." or "dot(arg0, ..."
            lhs_name = None
            if "dot(" in line:
                inner = line.split("dot(", 1)[1]
                pm = re.search(r"%([\w.\-]+)", inner)
                if pm is not None and pm.start() < inner.find(")"):
                    lhs_name = pm.group(1)
                else:  # typeless operand form: names only, commas top-level
                    first = inner.split(",", 1)[0].strip()
                    lhs_name = first.split()[-1] if first else None
            if cm is not None and lhs_name in shapes:
                lhs_dims = _shape_dims(shapes[lhs_name])
                k = math.prod(
                    lhs_dims[int(i)]
                    for i in cm.group(1).split(",")
                    if i and int(i) < len(lhs_dims)
                )
                dot_flops += 2.0 * math.prod(dims or (0,)) * k
        elif opcode == "parameter":
            if comp == entry_comp:
                entry_params += _shape_bytes(type_str)
        elif base in ("fusion", "custom-call"):
            fusion_bytes += _shape_bytes(type_str)
        if comp == entry_comp and line.lstrip().startswith("ROOT"):
            entry_out = _shape_bytes(type_str)

    phase_of = phases.finish()
    heads = {n: f"{shapes[n]} {op}({','.join(phases.operands[n])})"
             for _, n, _, op, _ in instrs if n in phase_of}
    return HLOAnalysis(
        collectives=CollectiveStats(ops=tuple(ops)),
        dot_flops=dot_flops,
        hbm_bytes=entry_params + entry_out + fusion_bytes,
        hbm_bytes_in=entry_params,
        hbm_bytes_out=entry_out,
        num_instructions=len(instrs),
        module=mm.group(1) if mm else "",
        phases=phase_of,
        heads=heads,
    )


def inter_client_all_reduces(
    analysis: HLOAnalysis, rules, param_count: int
) -> tuple[int, float]:
    """Count all-reduces that cross the plan's client axes AND carry the
    model-delta payload (≥ half the fused f32 delta bytes, which filters
    the metric-scalar traffic). The FedFog contract is exactly ONE such
    op per round when the client axes span more than one device; callers
    should skip the check when ``delta_bytes`` is returned with a
    single-way client axis (count is 0 by construction there).

    Returns (count, delta_bytes).
    """
    mesh_shape = rules.mesh.shape
    delta_bytes = 4.0 * param_count / max(mesh_shape.get("zero", 1), 1)
    count = count_axis_crossing(
        analysis,
        rules.mesh,
        axes=rules.plan.client_axes,
        kinds=("all-reduce",),
        min_bytes=0.5 * delta_bytes,
    )
    return count, delta_bytes


def _fog_axis_split(mesh, client_axes, fog_nodes: int):
    """Split the client axes into a fog-tier prefix and an edge-tier
    suffix: ``fog_nodes`` must equal the device product of a leading
    prefix of ``client_axes`` (mirrors kernels.delta_pipeline
    ``split_fog_axes``, re-derived here so dist stays dependency-free).
    Returns (fog_axes, edge_axes)."""
    prod = 1
    for i, a in enumerate(client_axes):
        if prod == fog_nodes:
            return tuple(client_axes[:i]), tuple(client_axes[i:])
        prod *= int(mesh.shape.get(a, 1))
    if prod == fog_nodes:
        return tuple(client_axes), ()
    raise ValueError(
        f"fog_nodes={fog_nodes} is not the device product of a leading "
        f"prefix of client axes {tuple(client_axes)} (mesh {dict(mesh.shape)})"
    )


def assert_inter_client_contract(
    analysis: HLOAnalysis, rules, param_count: int, fog_nodes: int = 1
) -> tuple[int, float]:
    """Post-compile guard for the paper's §III communication contract:
    exactly ONE delta-sized all-reduce crosses the client axes per
    compiled round. No-op (count 0 by construction) when the client
    axes span a single device. Returns (count, delta_bytes) so callers
    can log what they checked. Raises AssertionError on violation —
    both the reference fused-buffer aggregation and the sharded
    delta-pipeline kernel path must satisfy it.

    With ``fog_nodes > 1`` the contract becomes per-tier: the client
    axes split into a fog prefix and an edge suffix, and the compiled
    round must carry exactly ONE delta-sized all-reduce confined to the
    edge axes (the fog-local partial sum; zero when the edge suffix
    spans a single device) plus exactly ONE crossing the fog axes (the
    cloud combine). Returns (edge_count + fog_count, delta_bytes)."""
    count, delta_bytes = inter_client_all_reduces(analysis, rules, param_count)
    ways = getattr(rules, "client_ways", None)
    if ways is None:
        ways = math.prod(
            int(rules.mesh.shape.get(a, 1)) for a in rules.plan.client_axes
        )
    if fog_nodes > 1 and ways > 1:
        fog_axes, edge_axes = _fog_axis_split(
            rules.mesh, rules.plan.client_axes, fog_nodes
        )
        min_bytes = 0.5 * delta_bytes
        edge_ways = math.prod(
            int(rules.mesh.shape.get(a, 1)) for a in edge_axes
        )
        edge_count = count_axis_crossing(
            analysis, rules.mesh, axes=edge_axes,
            kinds=("all-reduce",), min_bytes=min_bytes, not_axes=fog_axes,
        )
        fog_count = count_axis_crossing(
            analysis, rules.mesh, axes=fog_axes,
            kinds=("all-reduce",), min_bytes=min_bytes, not_axes=edge_axes,
        )
        want_edge = 1 if edge_ways > 1 else 0
        if edge_count != want_edge or fog_count != 1:
            raise AssertionError(
                f"fog-tier collective contract violated: found "
                f"{edge_count} edge-tier (axes {edge_axes}, expected "
                f"{want_edge}) and {fog_count} fog-tier (axes "
                f"{fog_axes}, expected 1) delta-sized "
                f"({delta_bytes:.0f}B) all-reduces"
            )
        return edge_count + fog_count, delta_bytes
    if ways > 1 and count != 1:
        raise AssertionError(
            f"inter-client all-reduce contract violated: found {count} "
            f"delta-sized ({delta_bytes:.0f}B) all-reduces crossing "
            f"{tuple(rules.plan.client_axes)}, expected exactly 1"
        )
    return count, delta_bytes


def count_axis_crossing(
    analysis: HLOAnalysis,
    mesh,
    axes=("client",),
    kinds=("all-reduce",),
    min_bytes: float = 0.0,
    not_axes=(),
) -> int:
    """Number of collectives whose replica groups CROSS the given mesh
    axes — i.e. some group contains two devices with different coordinates
    along one of ``axes``. Partition ids index ``mesh.devices`` flattened
    row-major (the jit/GSPMD device-assignment order).

    ``min_bytes`` filters metric-scalar traffic so the model-delta
    aggregation can be isolated (the paper's one inter-client collective).
    ``not_axes`` additionally requires the op to stay CONFINED to slices
    of those axes (no group crosses them) — this is how the fog contract
    tells a tier-local psum from one flat all-reduce spanning both tiers.
    """
    names = list(mesh.axis_names)
    sizes = [int(mesh.shape[a]) for a in names]
    idxs = [names.index(a) for a in axes if a in names]
    not_idxs = [names.index(a) for a in not_axes if a in names]
    if not idxs:
        return 0
    total = math.prod(sizes)

    def crosses(groups, which) -> bool:
        if groups is None:
            return any(sizes[i] > 1 for i in which)
        for g in groups:
            coords = np.array(np.unravel_index(np.asarray(g) % total, sizes))
            for i in which:
                if len(set(coords[i].tolist())) > 1:
                    return True
        return False

    return sum(
        1
        for op in analysis.collectives.ops
        if op.kind in kinds
        and op.bytes >= min_bytes
        and crosses(op.groups, idxs)
        and not (not_idxs and crosses(op.groups, not_idxs))
    )
