"""Single extraction path over a compiled step: the ``StepAnatomy``.

A compiled XLA program already carries everything static performance
analysis needs — the cost model's FLOPs and bytes-accessed, the memory
analysis' argument/output/temp HBM bytes, and (in the optimized HLO text)
the full collective inventory: which collectives run, at what dtype, with
what payload, over which mesh axis. ``metrics/mfu.py`` and
``tools/memplan.py`` each grew a private probe over a slice of this;
this module is the one shared path, and the schema-versioned
:class:`StepAnatomy` is its output — consumed by ``analysis/roofline.py``
(time attribution), ``analysis/explain.py`` (``tpu-ddp analyze``),
``analysis/regress.py`` (``tpu-ddp bench compare``), and
``benchmarks/aot_v5e.py`` (per-program collective evidence).

Mesh-axis attribution is best-effort from the instruction's
``replica_groups`` / ``source_target_pairs`` against the mesh's row-major
logical device order (how GSPMD assigns flattened ids to a NamedSharding
mesh): a group set that matches "vary along one axis, fix the others"
gets that axis's name; the full-device group gets ``"all"``; anything
else ``"unknown"``.

Also here: the process-wide **compile cache** (``cached_compile``) keyed
on (strategy, shapes, flags) — ``tools/memplan.py`` routes through it so
comparing layouts of the same program (``--zero1`` with and without
``--grad-compress`` wire tables, docs-table sweeps) compiles each
distinct program once per process.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

#: bump on any breaking change to the StepAnatomy record shape
#: (v2: + ``program_order`` — the linearized collective schedule)
ANATOMY_SCHEMA_VERSION = 2

#: collective opcodes the inventory tracks (definition sites, sync or
#: async ``-start`` — ``-done`` halves are the same transfer)
COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all")

#: non-collective opcodes worth counting (fusion count is the anatomy's
#: "how hard did XLA work" figure; conv/custom-call mirror aot_v5e.py)
_OTHER_OPS = ("convolution", "fusion", "custom-call")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "c64": 8, "c128": 16,
}

_ARRAY_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"= (?P<result>[^=]*?)\s(?P<op>" + "|".join(COLLECTIVE_OPS) +
    r")(?:-start)?\("
)
_GROUPS_EXPLICIT_RE = re.compile(
    r"replica_groups=\{(\{[0-9,]*\}(?:,\{[0-9,]*\})*)\}"
)
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[([0-9,]+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?"
)
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)*)\}")
#: XLA annotates long tuple types with ``/*index=5*/`` markers — their
#: ``=`` would end ``_OP_RE``'s result group early
_INDEX_COMMENT_RE = re.compile(r"/\*index=\d+\*/")
#: an instruction definition: ``[ROOT] %name = <result type> opcode(...)``
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+) = (.*)$")
_NAME_RE = re.compile(r"%[\w.\-]+")


def _elem_count(dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def _array_bytes(segment: str) -> Dict[str, int]:
    """Sum bytes of every ``dtype[dims]`` array token in ``segment``,
    grouped by dtype. (Layout suffixes like ``{1,0}`` carry no brackets,
    so the token regex is unambiguous.)"""
    out: Dict[str, int] = {}
    for dtype, dims in _ARRAY_RE.findall(segment):
        width = _DTYPE_BYTES.get(dtype)
        if width is None:
            continue
        out[dtype] = out.get(dtype, 0) + _elem_count(dims) * width
    return out


def _operand_segment(line: str, open_idx: int) -> str:
    """Text between the opcode's ``(`` and its matching ``)`` — the
    operand list, whose types are the payload each device contributes."""
    depth = 0
    for i in range(open_idx, len(line)):
        ch = line[i]
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return line[open_idx + 1:i]
    return line[open_idx + 1:]


def _parse_groups(rest: str) -> Optional[List[Tuple[int, ...]]]:
    """replica_groups in either the explicit ``{{0,1},{2,3}}`` or the
    iota ``[g,s]<=[dims](T(perm))`` form -> list of id tuples."""
    m = _GROUPS_EXPLICIT_RE.search(rest)
    if m:
        groups = []
        for grp in re.findall(r"\{([0-9,]*)\}", m.group(1)):
            groups.append(tuple(int(x) for x in grp.split(",") if x))
        return groups or None
    m = _GROUPS_IOTA_RE.search(rest)
    if m:
        shape = [int(x) for x in m.group(1).split(",")]
        dims = [int(x) for x in m.group(2).split(",")]
        try:
            import numpy as np

            ids = np.arange(int(np.prod(dims))).reshape(dims)
            if m.group(3):
                perm = [int(x) for x in m.group(3).split(",")]
                ids = ids.transpose(perm)
            flat = ids.reshape(shape)
            return [tuple(int(x) for x in row) for row in flat]
        except Exception:
            return None
    return None


def _parse_pairs(rest: str) -> Optional[List[Tuple[int, int]]]:
    m = _PAIRS_RE.search(rest)
    if not m:
        return None
    return [(int(a), int(b))
            for a, b in re.findall(r"\{(\d+),(\d+)\}", m.group(1))]


def _nontrivial(mesh_shape: Dict[str, int]) -> Dict[str, int]:
    """Size-1 axes carry no collectives: MeshSpec materializes every named
    axis, so a 1-D data mesh arrives as (data=8, model=1, ...) — drop the
    trivial axes or everything attributes as "all"."""
    return {a: s for a, s in mesh_shape.items() if s > 1}


def _axis_of_groups(groups: Sequence[Tuple[int, ...]],
                    mesh_shape: Optional[Dict[str, int]]) -> str:
    """Name the mesh axis a replica-group set reduces over (row-major
    logical ids), ``"all"`` for the whole mesh, else ``"unknown"``."""
    mesh_shape = _nontrivial(mesh_shape or {})
    if not mesh_shape:
        return "unknown"
    try:
        import numpy as np

        axes = list(mesh_shape)
        sizes = [mesh_shape[a] for a in axes]
        n = int(np.prod(sizes))
        observed = frozenset(frozenset(g) for g in groups)
        if observed == frozenset({frozenset(range(n))}):
            return "all" if len(axes) > 1 else axes[0]
        ids = np.arange(n).reshape(sizes)
        for k, axis in enumerate(axes):
            moved = np.moveaxis(ids, k, -1).reshape(-1, sizes[k])
            expected = frozenset(frozenset(int(x) for x in row)
                                 for row in moved)
            if observed == expected:
                return axis
    except Exception:
        pass
    return "unknown"


def _axis_of_pairs(pairs: Sequence[Tuple[int, int]],
                   mesh_shape: Optional[Dict[str, int]]) -> str:
    """A permutation's axis: every (src, tgt) differs along exactly one
    (and the same) mesh coordinate."""
    mesh_shape = _nontrivial(mesh_shape or {})
    if not mesh_shape:
        return "unknown"
    try:
        import numpy as np

        axes = list(mesh_shape)
        sizes = [mesh_shape[a] for a in axes]
        hit = set()
        for s, t in pairs:
            cs = np.unravel_index(s, sizes)
            ct = np.unravel_index(t, sizes)
            diff = [k for k in range(len(axes)) if cs[k] != ct[k]]
            if len(diff) != 1:
                return "unknown"
            hit.add(axes[diff[0]])
        if len(hit) == 1:
            return hit.pop()
    except Exception:
        pass
    return "unknown"


@dataclasses.dataclass
class Collective:
    """One (kind, dtype, axis) bucket of the inventory.

    ``payload_bytes`` is the full logical tensor the collective moves
    (summed over occurrences): the operand bytes, scaled by the group
    size for all-gather (whose operand is each device's shard).
    ``wire_bytes`` applies the standard per-device ring model on top:
    2(g-1)/g x payload for all-reduce, (g-1)/g for all-gather /
    reduce-scatter / all-to-all, 1x for collective-permute."""

    kind: str
    dtype: str
    axis: str
    count: int
    payload_bytes: int
    wire_bytes: int
    group_size: int

    def key(self) -> str:
        # group_size is part of the identity: without it, two buckets that
        # differ only in group size (e.g. fsdp_tp all-gathers over the
        # model axis AND the data axis with no mesh attribution, both
        # "all-gather/f32/unknown") would shadow each other in the
        # inventory dict the compare gate diffs
        return f"{self.kind}/{self.dtype}/{self.axis}/g{self.group_size}"


def _wire_bytes(kind: str, payload: int, g: int) -> int:
    if g <= 1:
        return payload if kind == "collective-permute" else 0
    if kind == "all-reduce":
        return int(2 * (g - 1) / g * payload)
    if kind == "collective-permute":
        return payload
    return int((g - 1) / g * payload)


@dataclasses.dataclass
class ScheduledCollective:
    """ONE collective instruction in optimized-HLO text order — the unit
    the lint tier's COL001 (collective order / participation symmetry)
    reasons over, where :class:`Collective` is the aggregated bucket the
    inventory diff reasons over. ``dtype`` is the dominant (largest-
    payload) operand dtype; ``payload_bytes`` sums every operand dtype
    (all-gather scaled by group size — the operand is one shard).
    ``groups``/``pairs`` are the raw participation sets, kept so callers
    can verify every device takes part exactly once."""

    index: int
    kind: str
    dtype: str
    axis: str
    group_size: int
    payload_bytes: int
    groups: Optional[List[Tuple[int, ...]]]
    pairs: Optional[List[Tuple[int, int]]]

    def key(self) -> str:
        return f"{self.kind}/{self.dtype}/{self.axis}/g{self.group_size}"


def _result_type(rhs: str) -> str:
    """The result-type prefix of an instruction's right-hand side: a
    parenthesized tuple type, or everything up to the opcode's space."""
    if rhs.startswith("("):
        return "(" + _operand_segment(rhs, 0) + ")"
    return rhs.split(" ", 1)[0]


def _parsed_collectives(hlo_text: str, mesh_shape):
    """Yield ``_parse_collective_line`` results in text order. The
    optimized HLO names its operands without their types (``all-reduce(
    %fusion.3, %dot)``), so the walk keeps each computation's
    ``name -> result type`` table for the payload lookup."""
    defs: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        line = _INDEX_COMMENT_RE.sub("", line)
        m = _DEF_RE.match(line)
        if m is None:
            if line.rstrip().endswith("{"):  # a new computation's header
                defs = {}
            continue
        defs[m.group(1)] = _result_type(m.group(2))
        parsed = _parse_collective_line(line, mesh_shape, defs)
        if parsed is not None:
            yield parsed


def _parse_collective_line(line: str, mesh_shape, defs):
    """(kind, per-dtype payload bytes, groups, pairs, group size, axis)
    for one HLO collective definition line, or None. The shared parse
    behind the aggregated inventory AND the ordered schedule. ``defs``
    types the operands the line only names."""
    m = _OP_RE.search(line)
    if m is None:
        return None
    kind = m.group("op")
    operands = _operand_segment(line, line.index("(", m.end() - 1))
    if not _ARRAY_RE.search(operands):
        operands = " ".join(
            defs.get(name, "") for name in _NAME_RE.findall(operands))
    rest = line[m.end():]
    groups = _parse_groups(rest)
    pairs = _parse_pairs(rest)
    if kind == "collective-permute":
        g = len(pairs) if pairs else 0
        axis = _axis_of_pairs(pairs, mesh_shape) if pairs else "unknown"
    else:
        g = len(groups[0]) if groups else 0
        axis = (_axis_of_groups(groups, mesh_shape) if groups
                else "unknown")
    per_dtype = _array_bytes(operands)
    if kind == "all-gather" and g > 1:
        per_dtype = {d: n * g for d, n in per_dtype.items()}
    return kind, per_dtype, groups, pairs, g, axis


_MODULE_RE = re.compile(r"^HloModule\s+([\w.\-]+)")
_OP_NAME_RE = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_OPCODE_RE = re.compile(r"^\s*([\w\-]+)\(")
#: attributes that name a computation inlined into its caller: a fusion's
#: body, a reducer, a comparator. Their instructions never run as
#: operations of their own, so a device trace never prints them.
_INLINED_RE = re.compile(
    r"(?:to_apply|select|scatter|comparator)=(%[\w.\-]+)")
_FUSION_CALLS_RE = re.compile(r"\bcalls=(%[\w.\-]+)")
_HEADER_RE = re.compile(r"^(?:ENTRY\s+)?(%[\w.\-]+)\s.*\{\s*$")


def program_instructions(hlo_text: str) -> Tuple[str, List[Dict[str, Any]]]:
    """``(module name, instructions)`` of an optimised HLO text: the name a
    device trace prints for the program (``jit_shard_step``) and, for every
    instruction a trace can print, ``{"name", "opcode", "op_name",
    "operands", "body_op_names"}``: the name without its ``%``
    (``fusion.11``), the ``op_name`` of its ``metadata`` ("" where the
    compiler left none), the names of its operands and, for a fusion, the
    distinct ``op_name``s of the instructions fused into it. Instructions
    inside a fusion's body or a reducer are not listed themselves: they
    run as part of their caller, which is."""
    module = ""
    computations: Dict[str, List[Dict[str, Any]]] = {}
    inlined = set()
    current: List[Dict[str, Any]] = []
    for line in hlo_text.splitlines():
        line = _INDEX_COMMENT_RE.sub("", line)
        m = _DEF_RE.match(line)
        if m is None:
            header = _HEADER_RE.match(line)
            if header:
                current = computations.setdefault(header.group(1), [])
            elif not module:
                named = _MODULE_RE.match(line)
                module = named.group(1) if named else ""
            continue
        rest = m.group(2)[len(_result_type(m.group(2))):]
        found = _OPCODE_RE.match(rest)
        opcode = found.group(1) if found else ""
        op_name = _OP_NAME_RE.search(rest)
        calls = _FUSION_CALLS_RE.findall(rest) if opcode == "fusion" else []
        inlined.update(calls)
        if opcode not in ("fusion", "call"):
            inlined.update(_INLINED_RE.findall(rest))
        operands = _operand_segment(rest, found.end() - 1) if found else ""
        current.append({
            "name": m.group(1).lstrip("%"),
            "opcode": opcode,
            "op_name": op_name.group(1) if op_name else "",
            "operands": [n.lstrip("%") for n in _NAME_RE.findall(operands)],
            "calls": calls,
        })
    out = []
    for name, instructions in computations.items():
        if name in inlined:
            continue
        for ins in instructions:
            body = {inner["op_name"] for called in ins.pop("calls")
                    for inner in computations.get(called, ())}
            ins["body_op_names"] = sorted(body - {""})
            out.append(ins)
    return module, out


def collective_schedule(
    hlo_text: str, mesh_shape: Optional[Dict[str, int]] = None,
) -> List[ScheduledCollective]:
    """The linearized collective schedule: one entry per collective
    definition site, in optimized-HLO text order (topological within each
    computation — deterministic for a given compile, which is what the
    order pin needs; entries inside scan/while bodies appear where their
    computation is printed)."""
    out: List[ScheduledCollective] = []
    for parsed in _parsed_collectives(hlo_text, mesh_shape):
        kind, per_dtype, groups, pairs, g, axis = parsed
        if per_dtype:
            dtype = max(per_dtype, key=per_dtype.get)
        else:
            dtype = "unknown"
        out.append(ScheduledCollective(
            index=len(out), kind=kind, dtype=dtype, axis=axis,
            group_size=g, payload_bytes=sum(per_dtype.values()),
            groups=groups, pairs=pairs,
        ))
    return out


def extract_collectives(
    hlo_text: str, mesh_shape: Optional[Dict[str, int]] = None,
) -> List[Collective]:
    """Parse the optimized HLO's collective definition sites into the
    aggregated inventory, sorted by descending wire bytes."""
    buckets: Dict[Tuple[str, str, str, int], Dict[str, int]] = {}
    for parsed in _parsed_collectives(hlo_text, mesh_shape):
        kind, per_dtype, _groups, _pairs, g, axis = parsed
        for dtype, nbytes in per_dtype.items():
            b = buckets.setdefault((kind, dtype, axis, g),
                                   {"count": 0, "payload": 0, "wire": 0})
            b["count"] += 1
            b["payload"] += nbytes
            b["wire"] += _wire_bytes(kind, nbytes, g)
    out = [
        Collective(kind=k, dtype=d, axis=a, count=b["count"],
                   payload_bytes=b["payload"], wire_bytes=b["wire"],
                   group_size=g)
        for (k, d, a, g), b in buckets.items()
    ]
    out.sort(key=lambda c: (-c.wire_bytes, c.kind, c.dtype))
    return out


def hlo_op_counts(hlo_text: str) -> Dict[str, int]:
    """Instruction counts of the load-bearing opcodes in the optimized
    HLO (definition sites only — operand uses, instruction names, and
    ``-done`` halves excluded). The shared implementation behind
    ``benchmarks/aot_v5e.py``'s per-program ``hlo_ops``."""
    found = re.findall(
        r"[\]})] (" + "|".join(COLLECTIVE_OPS + _OTHER_OPS) +
        r")(?:-start)?\(",
        hlo_text,
    )
    out: Dict[str, int] = {}
    for op in found:
        out[op] = out.get(op, 0) + 1
    return out


@dataclasses.dataclass
class StepAnatomy:
    """Schema-versioned static anatomy of ONE compiled train step.

    All sizes are PER DEVICE (XLA reports the partitioned per-device
    program); ``flops``/``bytes_accessed`` are the cost model's figures
    for one call, ``None`` where the backend exposes none."""

    strategy: str
    model: str
    device_kind: str
    mesh: Dict[str, int]
    n_devices: int
    per_shard_batch: Optional[int]
    compute_dtype: Optional[str]
    flops: Optional[float]
    bytes_accessed: Optional[float]
    argument_bytes: Optional[int]
    output_bytes: Optional[int]
    temp_bytes: Optional[int]
    generated_code_bytes: Optional[int]
    fusion_count: int
    hlo_ops: Dict[str, int]
    collectives: List[Collective]
    #: inventory keys in optimized-HLO program order (one entry per
    #: collective instruction, dominant dtype) — the schedule COL001 and
    #: the compare gate's reorder check pin; [] on pre-v2 records
    program_order: List[str] = dataclasses.field(default_factory=list)
    schema_version: int = ANATOMY_SCHEMA_VERSION

    @property
    def peak_bytes(self) -> Optional[int]:
        """Steady-state estimate: donated args alias outputs, so peak is
        roughly arguments + temps (memplan's long-standing convention)."""
        if self.argument_bytes is None or self.temp_bytes is None:
            return None
        return self.argument_bytes + self.temp_bytes

    def inventory(self) -> Dict[str, Dict[str, int]]:
        """``{"kind/dtype/axis/gN": {count, payload_bytes, wire_bytes}}``
        — the comparison key ``bench compare`` diffs."""
        return {
            c.key(): {"count": c.count, "payload_bytes": c.payload_bytes,
                      "wire_bytes": c.wire_bytes,
                      "group_size": c.group_size}
            for c in self.collectives
        }

    def collective_kinds(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for c in self.collectives:
            out[c.kind] = out.get(c.kind, 0) + c.count
        return out

    def to_json(self) -> dict:
        rec = dataclasses.asdict(self)
        rec["peak_bytes"] = self.peak_bytes
        rec["inventory"] = self.inventory()
        return rec

    @classmethod
    def from_json(cls, rec: dict) -> "StepAnatomy":
        version = rec.get("schema_version", 0)
        if version > ANATOMY_SCHEMA_VERSION:
            raise ValueError(
                f"anatomy schema_version {version} is newer than this "
                f"tool understands ({ANATOMY_SCHEMA_VERSION})"
            )
        fields = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in rec.items() if k in fields}
        kw["collectives"] = [
            Collective(**c) for c in rec.get("collectives", ())
        ]
        return cls(**kw)


def cost_analysis_figures(compiled) -> Tuple[Optional[float],
                                             Optional[float]]:
    """(flops, bytes accessed) per XLA's cost model of the compiled
    executable, each None when absent/zero (some CPU builds expose no
    cost analysis). The shared probe behind ``metrics/mfu.py``."""
    try:
        analysis = compiled.cost_analysis()
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0] if analysis else {}
        flops = float(analysis.get("flops", -1.0))
        accessed = float(analysis.get("bytes accessed", -1.0))
        return (flops if flops > 0 else None,
                accessed if accessed > 0 else None)
    except Exception:
        return None, None


def extract_anatomy(
    compiled,
    *,
    strategy: str = "unknown",
    model: str = "unknown",
    mesh: Any = None,
    device_kind: str = "unknown",
    per_shard_batch: Optional[int] = None,
    compute_dtype: Optional[str] = None,
) -> StepAnatomy:
    """The single extraction path: one ``jax.stages.Compiled`` in, one
    :class:`StepAnatomy` out. ``mesh`` may be a ``jax.sharding.Mesh`` or
    a plain ``{axis: size}`` dict (used for axis attribution)."""
    mesh_shape: Optional[Dict[str, int]] = None
    if mesh is not None:
        if isinstance(mesh, dict):
            mesh_shape = dict(mesh)
        else:
            mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
            if device_kind == "unknown":
                kinds = {d.device_kind for d in mesh.devices.flat}
                if len(kinds) == 1:
                    device_kind = kinds.pop()
    n_devices = 1
    for size in (mesh_shape or {}).values():
        n_devices *= size

    flops, bytes_accessed = cost_analysis_figures(compiled)

    arg = out = temp = code = None
    try:
        ma = compiled.memory_analysis()
        arg = int(ma.argument_size_in_bytes)
        out = int(ma.output_size_in_bytes)
        temp = int(ma.temp_size_in_bytes)
        code = int(getattr(ma, "generated_code_size_in_bytes", 0)) or None
    except Exception:
        pass

    try:
        text = compiled.as_text()
    except Exception:
        text = ""
    ops = hlo_op_counts(text)
    return StepAnatomy(
        strategy=strategy,
        model=model,
        device_kind=device_kind,
        mesh=mesh_shape or {},
        n_devices=n_devices,
        per_shard_batch=per_shard_batch,
        compute_dtype=compute_dtype,
        flops=flops,
        bytes_accessed=bytes_accessed,
        argument_bytes=arg,
        output_bytes=out,
        temp_bytes=temp,
        generated_code_bytes=code,
        fusion_count=ops.get("fusion", 0),
        hlo_ops=ops,
        collectives=extract_collectives(text, mesh_shape),
        program_order=[c.key()
                       for c in collective_schedule(text, mesh_shape)],
    )


# -- process-wide compile cache -------------------------------------------

_COMPILE_CACHE: Dict[Any, Any] = {}
_CACHE_STATS = {"hits": 0, "misses": 0}


def cached_compile(key: Any, build) -> Any:
    """``build()`` -> compiled, memoized on ``key`` for the process
    lifetime. Callers key on everything that determines the compiled
    program — (strategy, model, shapes, dtype, flags, topology) — so a
    sweep comparing layouts of the same program (memplan's
    ``--zero1 --grad-compress`` tables, ``tpu-ddp analyze --strategy all``'s
    loop) compiles each distinct program once."""
    if key in _COMPILE_CACHE:
        _CACHE_STATS["hits"] += 1
        return _COMPILE_CACHE[key]
    _CACHE_STATS["misses"] += 1
    compiled = build()
    _COMPILE_CACHE[key] = compiled
    return compiled


def compile_cache_stats() -> Dict[str, int]:
    return dict(_CACHE_STATS)


def clear_compile_cache() -> None:
    _COMPILE_CACHE.clear()
    _CACHE_STATS["hits"] = _CACHE_STATS["misses"] = 0
