"""``tpu-ddp lint`` — static verifier for every compiled train step.

PR 5 made the compiler the primary observability source; this module
makes it a *gate*: a rule-based static verifier that runs on CPU, before
any TPU run, over three tiers of every strategy's step program —

- the **compiled HLO** (via ``build_abstract_step`` + the shared compile
  cache): buffer-donation accounting, physical input layouts, the
  linearized collective schedule, host-transfer ops;
- the **jaxpr** of the step function: backend-independent dtypes (the
  optimized HLO is useless for dtype audits on CPU, which legalizes bf16
  arrays to f32) and host-callback primitives;
- an **AST tier** over ``tpu_ddp/`` source: recompile hazards no
  compiled artifact can show (a jit created per loop iteration never
  *looks* wrong in any one program).

Rules (each with an id, severity, and a one-line fix hint — the table
renders in docs/lint.md):

- **DON001** donation audit — the train state must be donated: the
  compiled ``argument_bytes − aliased bytes`` must match the batch's
  per-device bytes (memplan's accounting, reused as the oracle). A
  dropped ``donate_argnums`` silently doubles peak HBM.
- **DTY001** dtype-widening audit — in a bf16-compute program, no big
  f32 tensor op (dot/conv) and no f32 collective payload beyond the
  mixed-precision allowlist budget (f32 master-weight grad sync, loss,
  norms, optimizer moments, health stats). An accidental f32 upcast
  halves effective ICI/HBM bandwidth.
- **SHD001** replication audit — for zero1/fsdp/fsdp_tp/ep programs, the
  big opt-state/param leaves must come out of the compiler physically
  sharded (the 1/N layout ZeRO requires), not replicated.
- **COL001** collective order/participation audit — every collective's
  replica groups must partition the whole mesh (a device missing from a
  group set is a multihost deadlock), every permute must be a valid
  permutation, and the linearized schedule must match the strategy's
  pinned fingerprint and order (grads sync BEFORE params gather back).
- **XFR001** host-transfer audit — no infeed/outfeed/host callbacks
  inside the step (each one is a device->host sync in the hot loop).
- **RCP001** recompile-hazard AST rule — jit built inside a loop,
  unhashable (mutable) defaults on jitted functions, and wall-clock /
  np.random trace-time constants inside the step factories.
- **KRN001** fused-kernel capability audit — a config that enables the
  Pallas kernel switch (``--kernels``) on a backend with no Pallas
  lowering fails CLOSED: the rule names every fused kernel the switch
  would silently skip and the XLA reference each falls back to.

``tpu-ddp lint --strategy all`` verifies all nine strategy programs
(incl. the ``--zero1`` / ``--grad-compress`` layout overlays) plus the
source tier; ``--json`` writes a machine artifact whose per-rule counts
``tpu-ddp bench compare`` gates exactly like a collective regression.
The Trainer's ``--lint-on-start`` runs the program rules over the REAL
jitted step (not the abstract twin) and refuses to launch on a finding.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

from tpu_ddp.analysis.hlo import (
    cached_compile,
    collective_schedule,
    extract_anatomy,
)

#: bump on any breaking change to the lint artifact shape
LINT_SCHEMA_VERSION = 1

#: rule registry: id -> (what it catches, the one-line fix hint) — the
#: single source behind findings and the docs/lint.md rule table
RULES: Dict[str, Dict[str, str]] = {
    "DON001": {
        "title": "donation audit",
        "fix": "jit the train step with donate_argnums=(0,) (the "
               "builders' donate=True) so the state aliases its output",
    },
    "DTY001": {
        "title": "dtype-widening audit",
        "fix": "keep big tensor ops and collective payloads bf16 in a "
               "bf16 program (cast at the op, or raise the allowlist "
               "budget in LintConfig if the f32 traffic is deliberate)",
    },
    "SHD001": {
        "title": "replication audit",
        "fix": "attach the partition's state shardings (P over the shard "
               "axis) to the state before compiling — a replicated "
               "opt-state leaf forfeits the 1/N layout ZeRO pays for",
    },
    "COL001": {
        "title": "collective order/participation audit",
        "fix": "keep ONE deterministic collective schedule: every group "
               "set must partition the whole mesh, permutes must be "
               "permutations, and grads sync before params gather back",
    },
    "XFR001": {
        "title": "host-transfer audit",
        "fix": "remove debug/io/host callbacks from the compiled step — "
               "log from the host loop (or the telemetry sinks) instead",
    },
    "RCP001": {
        "title": "recompile-hazard audit",
        "fix": "hoist jax.jit out of loops, keep jitted-function "
               "defaults hashable, and bake no wall-clock/np.random "
               "values into traced code",
    },
    "KRN001": {
        "title": "fused-kernel capability audit",
        "fix": "run with --kernels only where Pallas can execute "
               "(mosaic on TPU, the interpreter on CPU) — or drop the "
               "switch and keep the named XLA fallback path explicitly",
    },
}


@dataclasses.dataclass
class LintFinding:
    """One rule violation. ``severity`` is ``"error"`` (fails the lint
    exit code / the preflight) or ``"warning"`` (reported only)."""

    rule: str
    severity: str
    program: str        # strategy name, or "source" for the AST tier
    message: str
    fix: str = ""
    location: str = ""  # file:line for the AST tier

    def render(self) -> str:
        loc = f" ({self.location})" if self.location else ""
        out = (f"  {self.rule} [{self.severity}] {self.program}: "
               f"{self.message}{loc}")
        if self.fix:
            out += f"\n      fix: {self.fix}"
        return out

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def _finding(rule: str, program: str, message: str,
             severity: str = "error", location: str = "") -> LintFinding:
    return LintFinding(rule=rule, severity=severity, program=program,
                       message=message, fix=RULES[rule]["fix"],
                       location=location)


@dataclasses.dataclass
class LintConfig:
    """Thresholds. The defaults are tuned so every in-tree strategy
    passes clean on the CPU mesh AND the injected violations the tests
    plant are caught with wide margin."""

    #: DON001: non-donated argument bytes allowed beyond the batch
    #: (step counters, small non-aliasable leaves); the 2% floor in
    #: check_donation scales it for big programs
    donation_slack_bytes: int = 64 * 1024
    #: DTY001: a single f32 dot/conv output below this is allowlisted
    #: (loss head, norms, health stats are all tiny)
    big_op_bytes: int = 1 << 20
    #: DTY001: total f32 collective payload allowed, as a multiple of
    #: the f32 param bytes (the mixed-precision master-weight grad sync:
    #: 1x for dp's all-reduce, 2x for zero1's reduce-scatter +
    #: all-gather) plus a flat floor for loss/norm/moment scalars
    f32_collective_budget_factor: float = 2.5
    f32_collective_budget_floor: int = 1 << 20
    #: SHD001: a state leaf below this many global bytes is not expected
    #: to be sharded (biases, scalars)
    big_leaf_bytes: int = 8 * 1024
    #: SHD001: minimum fraction of big-leaf bytes that must be
    #: physically sharded in the sections the strategy scatters
    min_sharded_fraction: float = 0.5


# -- jaxpr tier -----------------------------------------------------------

#: cross-device transfer primitives as they appear in jaxprs (shard_map
#: family; the GSPMD family's collectives are partitioner-inserted and
#: audited on the HLO tier instead)
COLLECTIVE_PRIMS = frozenset({
    "psum", "psum_invariant", "pmax", "pmin", "all_gather",
    "all_gather_invariant", "reduce_scatter", "ppermute", "all_to_all",
})

#: host-callback primitives — any of these inside a step is a
#: device->host round trip per step
CALLBACK_PRIMS = frozenset({
    "debug_callback", "pure_callback", "io_callback", "callback",
    "outside_call", "host_callback_call",
})


def iter_jaxpr_eqns(jaxpr):
    """Yield every eqn of ``jaxpr`` and (recursively) of every sub-jaxpr
    in its params — pjit/shard_map/scan/cond bodies included."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    stack = [jaxpr]
    while stack:
        jx = stack.pop()
        if isinstance(jx, ClosedJaxpr):
            jx = jx.jaxpr
        for eqn in jx.eqns:
            yield eqn
            for val in eqn.params.values():
                vals = val if isinstance(val, (tuple, list)) else (val,)
                for v in vals:
                    if isinstance(v, (Jaxpr, ClosedJaxpr)):
                        stack.append(v)


def _aval_bytes(aval) -> int:
    import numpy as np

    try:
        return int(np.prod(aval.shape or (1,))) * aval.dtype.itemsize
    except Exception:
        return 0


def _tree_bytes(tree, *, dtypes: Optional[Tuple[str, ...]] = None) -> int:
    import jax

    total = 0
    for leaf in jax.tree.leaves(tree):
        dt = str(getattr(leaf, "dtype", ""))
        if dtypes is None or dt in dtypes:
            total += _aval_bytes(leaf)
    return total


# -- the per-program audit ------------------------------------------------

@dataclasses.dataclass
class ProgramAudit:
    """Everything the program rules read, gathered once per program."""

    program: str               # display/strategy label
    strategy: str              # fingerprint key
    compute_dtype: str
    mesh_shape: Dict[str, int]
    n_devices: int
    device_kind: str
    compiled: Any
    jaxpr: Any                 # ClosedJaxpr of the traced step
    hlo_text: str
    anatomy: Any               # StepAnatomy
    state: Any                 # the (abstract) input TrainState
    batch: Dict[str, Any]


def audit_program(step, state, batch, mesh, *, strategy: str,
                  compute_dtype: str = "float32",
                  cache_key: Any = None,
                  program: Optional[str] = None,
                  model_name: str = "unknown") -> ProgramAudit:
    """Trace + compile ``step(state, batch)`` (through the shared compile
    cache when ``cache_key`` is given) and gather the audit inputs."""
    traced = step.trace(state, batch)
    if cache_key is not None:
        compiled = cached_compile(cache_key,
                                  lambda: traced.lower().compile())
    else:
        compiled = traced.lower().compile()
    try:
        hlo_text = compiled.as_text()
    except Exception:
        hlo_text = ""
    anatomy = extract_anatomy(
        compiled, strategy=strategy, mesh=mesh, model=model_name,
        compute_dtype=compute_dtype,
    )
    mesh_shape = dict(zip(mesh.axis_names,
                          (int(s) for s in mesh.devices.shape)))
    n = 1
    for s in mesh_shape.values():
        n *= s
    return ProgramAudit(
        program=program or strategy, strategy=strategy,
        compute_dtype=compute_dtype, mesh_shape=mesh_shape, n_devices=n,
        device_kind=anatomy.device_kind, compiled=compiled,
        jaxpr=traced.jaxpr, hlo_text=hlo_text, anatomy=anatomy,
        state=state, batch=batch,
    )


def _per_device_bytes(leaf, mesh_shape: Dict[str, int]) -> int:
    """Bytes of one input leaf per device, from its (Named)Sharding spec
    — replicated when no sharding is attached."""
    total = _aval_bytes(leaf)
    sharding = getattr(leaf, "sharding", None)
    spec = getattr(sharding, "spec", None)
    if spec is None:
        return total
    div = 1
    for entry in spec:
        axes = entry if isinstance(entry, (tuple, list)) else (entry,)
        for axis in axes:
            if axis is not None:
                div *= mesh_shape.get(axis, 1)
    return total // max(div, 1)


# -- DON001: donation -----------------------------------------------------

def donation_report(compiled, batch, mesh_shape: Dict[str, int]) -> dict:
    """The donation accounting DON001 gates on — also surfaced in
    ``tools/memplan.py``'s report: per-device argument/output bytes, the
    bytes XLA aliased input->output (the donated state), and what the
    non-donated argument remainder should be (the batch; exact on CPU,
    an upper bound on TPU where argument buffers carry layout padding —
    which is why the GATE compares the donated bytes against the output
    side instead: the new state is the output, so a dropped donation
    shows up as output bytes with no input alias on every backend)."""
    import jax

    ma = compiled.memory_analysis()
    arg = int(ma.argument_size_in_bytes)
    out = int(ma.output_size_in_bytes)
    alias = int(getattr(ma, "alias_size_in_bytes", 0))
    batch_pd = sum(
        _per_device_bytes(leaf, mesh_shape)
        for leaf in jax.tree.leaves(batch)
    )
    return {
        "argument_bytes": arg,
        "output_bytes": out,
        "donated_bytes": alias,
        "undonated_output_bytes": out - alias,
        "non_donated_bytes": arg - alias,
        "expected_non_donated_bytes": batch_pd,
    }


def check_donation(audit: ProgramAudit,
                   cfg: LintConfig) -> List[LintFinding]:
    try:
        rep = donation_report(audit.compiled, audit.batch, audit.mesh_shape)
    except Exception as e:  # backend without memory analysis
        return [_finding(
            "DON001", audit.program,
            f"donation audit unavailable on this backend ({e})",
            severity="warning")]
    # outputs = the new state (+ small metrics): every output byte that
    # did NOT alias an input is a state byte double-buffered each step
    slack = max(cfg.donation_slack_bytes, rep["output_bytes"] // 50)
    excess = rep["undonated_output_bytes"]
    if excess > slack:
        return [_finding(
            "DON001", audit.program,
            f"train state is not (fully) donated: only "
            f"{rep['donated_bytes']} of {rep['output_bytes']} output "
            f"bytes alias a donated input — {excess} B of state is "
            f"double-buffered every step (argument_bytes="
            f"{rep['argument_bytes']}, batch accounts for "
            f"{rep['expected_non_donated_bytes']} B of the non-donated "
            "remainder)",
        )]
    return []


# -- DTY001: dtype widening ----------------------------------------------

_WIDE = ("float32", "float64")


def check_dtype_widening(audit: ProgramAudit,
                         cfg: LintConfig) -> List[LintFinding]:
    if audit.compute_dtype != "bfloat16":
        return []
    findings: List[LintFinding] = []
    big_ops: List[Tuple[str, int]] = []
    f32_collectives: List[Tuple[str, int]] = []
    for eqn in iter_jaxpr_eqns(audit.jaxpr):
        name = eqn.primitive.name
        if name in ("dot_general", "conv_general_dilated"):
            for v in eqn.outvars:
                if (str(v.aval.dtype) in _WIDE
                        and _aval_bytes(v.aval) > cfg.big_op_bytes):
                    big_ops.append((name, _aval_bytes(v.aval)))
        elif name in COLLECTIVE_PRIMS:
            nbytes = sum(_aval_bytes(v.aval) for v in eqn.outvars
                         if str(v.aval.dtype) in _WIDE)
            if nbytes:
                f32_collectives.append((name, nbytes))
    if big_ops:
        big_ops.sort(key=lambda t: -t[1])
        head = ", ".join(f"{k}[{n} B]" for k, n in big_ops[:3])
        findings.append(_finding(
            "DTY001", audit.program,
            f"{len(big_ops)} f32 tensor op(s) above "
            f"{cfg.big_op_bytes} B in a bf16-compute program "
            f"(largest: {head}) — the MXU runs them at half rate",
        ))
    # allowlist budget: the f32 master-weight gradient sync (+ zero1's
    # f32 param all-gather) is mixed-precision-correct; loss, norms,
    # optimizer moments, and health stats are all under the floor
    params_f32 = _tree_bytes(getattr(audit.state, "params", ()),
                             dtypes=_WIDE)
    budget = int(cfg.f32_collective_budget_factor * params_f32
                 + cfg.f32_collective_budget_floor)
    total = sum(n for _, n in f32_collectives)
    if total > budget:
        f32_collectives.sort(key=lambda t: -t[1])
        head = ", ".join(f"{k}[{n} B]" for k, n in f32_collectives[:3])
        findings.append(_finding(
            "DTY001", audit.program,
            f"f32 collective payload {total} B exceeds the "
            f"mixed-precision allowlist budget {budget} B "
            f"(2.5x f32 param bytes + 1 MiB; largest: {head}) — "
            "a widened payload halves effective ICI bandwidth",
        ))
    # the optimized-HLO inventory is only dtype-faithful off-CPU
    # (XLA:CPU legalizes bf16 arrays to f32)
    if "cpu" not in audit.device_kind.lower():
        hlo_total = sum(
            c.payload_bytes for c in audit.anatomy.collectives
            if c.dtype in ("f32", "f64"))
        if hlo_total > budget:
            findings.append(_finding(
                "DTY001", audit.program,
                f"optimized HLO carries {hlo_total} B of f32 collective "
                f"payload (budget {budget} B) in a bf16 program",
            ))
    return findings


# -- SHD001: physical replication ----------------------------------------

#: strategy -> (state sections whose big leaves must be sharded, mode):
#: "fraction" = at least min_sharded_fraction of big-leaf bytes;
#: "any" = at least one big leaf (ep shards only the expert tensors)
_SHARDED_SECTIONS = {
    "zero1": (("opt_state",), "fraction"),
    "zero3": (("params", "opt_state"), "fraction"),
    "fsdp": (("params", "opt_state"), "fraction"),
    "fsdp_tp": (("params", "opt_state"), "fraction"),
    "ep": (("params",), "any"),
}


def _input_layouts(audit: ProgramAudit):
    """[(section, pathstr, global bytes, expected sharding or None,
    physical sharding)] for every train-state leaf, by zipping the
    compiled executable's input shardings against the input tree."""
    import jax
    from jax.tree_util import keystr, tree_flatten, tree_flatten_with_path

    args_shardings, _ = audit.compiled.input_shardings
    flat_sh, _ = tree_flatten(args_shardings)
    flat_leaves = tree_flatten_with_path((audit.state, audit.batch))[0]
    if len(flat_sh) != len(flat_leaves):
        return []
    out = []
    for (path, leaf), phys in zip(flat_leaves, flat_sh):
        if not (path and isinstance(path[0], jax.tree_util.SequenceKey)
                and path[0].idx == 0):
            continue  # batch leaf
        section = getattr(path[1], "name", str(path[1])) if len(path) > 1 \
            else ""
        out.append((section, keystr(path), _aval_bytes(leaf),
                    getattr(leaf, "sharding", None), phys))
    return out


def check_replication(audit: ProgramAudit,
                      cfg: LintConfig) -> List[LintFinding]:
    spec = _SHARDED_SECTIONS.get(audit.strategy)
    layouts = _input_layouts(audit)
    findings: List[LintFinding] = []
    # leaf-wise: a leaf whose spec SAYS sharded must not bind replicated
    for section, path, nbytes, expected, phys in layouts:
        if nbytes < cfg.big_leaf_bytes:
            continue
        exp_sharded = (expected is not None
                       and not getattr(expected, "is_fully_replicated", True))
        if exp_sharded and getattr(phys, "is_fully_replicated", False):
            findings.append(_finding(
                "SHD001", audit.program,
                f"{path} ({nbytes} B): spec says sharded but the "
                "compiled executable binds it fully replicated",
            ))
    if spec is None:
        return findings
    sections, mode = spec
    big = [(s, p, n, phys) for s, p, n, _e, phys in layouts
           if s in sections and n >= cfg.big_leaf_bytes]
    if not big:
        return findings
    total = sum(n for _, _, n, _ in big)
    sharded = sum(n for _, _, n, phys in big
                  if not getattr(phys, "is_fully_replicated", True))
    if mode == "any":
        if sharded == 0:
            findings.append(_finding(
                "SHD001", audit.program,
                f"no big {'/'.join(sections)} leaf is physically sharded "
                f"({len(big)} leaves, {total} B all replicated) — the "
                f"{audit.strategy} layout requires a 1/N scatter",
            ))
    elif sharded < cfg.min_sharded_fraction * total:
        findings.append(_finding(
            "SHD001", audit.program,
            f"only {sharded}/{total} B of big {'/'.join(sections)} "
            f"leaves are physically sharded (< "
            f"{cfg.min_sharded_fraction:.0%}) — the {audit.strategy} "
            "layout requires the 1/N scatter ZeRO pays for",
        ))
    return findings


# -- COL001: collective order / participation ----------------------------

#: strategy -> [(late kind, early kinds, why)]: the first occurrence of
#: `late` must come after the first occurrence of one of `early`
ORDER_PINS = {
    # ZeRO-1: grads reduce-scatter down, THEN params all-gather back —
    # a gather first would train on stale params
    "zero1": [("all-gather", ("reduce-scatter", "all-reduce"),
               "params must gather back AFTER the gradient sync")],
    # ZeRO-3: the step OPENS with the prefetch all-gathers (block 0's
    # params are needed before anything computes); the grad sync belongs
    # to the tail — a sync-first schedule means params were not streamed
    "zero3": [("reduce-scatter", ("all-gather",),
               "the grad reduce-scatter belongs after the prefetch "
               "all-gathers (params stream in before anything computes)"),
              ("all-reduce", ("all-gather",),
               "every sync (loss/health/grad) belongs after the first "
               "prefetch all-gather")],
}


# -- COL001 (zero3): the prefetch-schedule contract ------------------------

_Z3_GATHER_RE = re.compile(r"[\]})] all-gather(?:-start)?\(")


def _check_zero3_prefetch(audit: ProgramAudit) -> List[LintFinding]:
    """The zero3 schedule contract, checked fail-closed on the COMPILED
    program: every parameter block must have its own prefetch-scoped
    all-gather group (``tpu_ddp.zero3_prefetch/b<k>`` — the named scopes
    survive into the optimized HLO's op_name metadata), no all-gather may
    live outside the prefetch schedule (an unscoped gather is either the
    serialized just-in-time schedule or a backward re-gather, both of
    which void the streaming claim), and the traced program must carry
    the ``zero3_handoff`` optimization barriers that chain block k+1's
    gather ahead of block k's first consuming op (XLA erases the barriers
    after scheduling, so they are checked in the jaxpr, where the
    double-buffer structure is still explicit). A program with none of
    the scopes — e.g. the injected serialized gather — fails closed."""
    from tpu_ddp.parallel.collectives import (
        ZERO3_HANDOFF_SCOPE,
        ZERO3_PREFETCH_SCOPE,
    )
    from tpu_ddp.parallel.zero import param_blocks

    findings: List[LintFinding] = []
    try:
        n_blocks = len(param_blocks(audit.state.params)[1])
    except Exception:
        n_blocks = 0
    prefetch_re = re.compile(re.escape(ZERO3_PREFETCH_SCOPE) + r"(\d+)")

    first_pos: Dict[int, int] = {}
    stray = 0
    for pos, line in enumerate(audit.hlo_text.splitlines()):
        if _Z3_GATHER_RE.search(line) is None:
            continue
        m = prefetch_re.search(line)
        if m is not None:
            first_pos.setdefault(int(m.group(1)), pos)
        else:
            stray += 1

    if not first_pos:
        findings.append(_finding(
            "COL001", audit.program,
            "zero3 prefetch schedule absent: no all-gather in the "
            "compiled step carries a "
            f"{ZERO3_PREFETCH_SCOPE}<k> scope — the parameter gathers "
            "are serialized/just-in-time (or params were never "
            "streamed), so the double-buffered overlap the --zero3 "
            "contract promises does not exist in this program",
        ))
        return findings
    missing = sorted(set(range(n_blocks)) - set(first_pos))
    if missing:
        findings.append(_finding(
            "COL001", audit.program,
            f"zero3 prefetch schedule incomplete: parameter blocks "
            f"{missing} of {n_blocks} have no prefetch-scoped all-gather "
            "in the compiled step (their params reach compute without a "
            "scheduled gather slot)",
        ))
    if stray:
        findings.append(_finding(
            "COL001", audit.program,
            f"zero3 re-gather: {stray} all-gather(s) outside the "
            "prefetch schedule — the backward (or a second forward "
            "assembly) is re-gathering full params; the zero3 contract "
            "is ONE scheduled gather per block per step, grads "
            "reduce-scatter straight into shard space",
        ))

    # the double-buffer handoff chain: checked in the TRACED program —
    # barriers order the schedule, then XLA erases them post-scheduling
    def _count_handoffs(jx) -> int:
        count = 0
        for eqn in jx.eqns:
            if eqn.primitive.name == "optimization_barrier":
                ns = str(getattr(eqn.source_info, "name_stack", ""))
                if ZERO3_HANDOFF_SCOPE in ns:
                    count += 1
            for v in eqn.params.values():
                if hasattr(v, "eqns"):
                    count += _count_handoffs(v)
                elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
                    count += _count_handoffs(v.jaxpr)
        return count

    handoffs = 0
    closed = getattr(audit.jaxpr, "jaxpr", audit.jaxpr)
    if closed is not None and hasattr(closed, "eqns"):
        handoffs = _count_handoffs(closed)
    if n_blocks > 1 and handoffs < n_blocks - 1:
        findings.append(_finding(
            "COL001", audit.program,
            f"zero3 double-buffer chain broken: {handoffs} "
            f"{ZERO3_HANDOFF_SCOPE}<k> optimization barrier(s) in the "
            f"traced step, expected >= {n_blocks - 1} (one per adjacent "
            "block pair) — without the handoff ties nothing pins block "
            "k+1's gather ahead of block k's first consuming op",
        ))
    return findings


def check_collective_order(audit: ProgramAudit, cfg: LintConfig,
                           schedule=None) -> List[LintFinding]:
    del cfg
    findings: List[LintFinding] = []
    if schedule is None:
        schedule = collective_schedule(audit.hlo_text, audit.mesh_shape)
    n = audit.n_devices
    all_ids = frozenset(range(n))
    for entry in schedule:
        if entry.groups:
            seen: List[int] = []
            for g in entry.groups:
                seen.extend(g)
            if len(seen) != len(set(seen)) or set(seen) != all_ids:
                findings.append(_finding(
                    "COL001", audit.program,
                    f"collective #{entry.index} ({entry.kind}) replica "
                    f"groups {entry.groups} do not partition the "
                    f"{n}-device mesh — devices left out of a group set "
                    "never join the rendezvous (multihost deadlock)",
                ))
        if entry.pairs:
            srcs = [s for s, _ in entry.pairs]
            tgts = [t for _, t in entry.pairs]
            if len(set(srcs)) != len(srcs) or len(set(tgts)) != len(tgts):
                findings.append(_finding(
                    "COL001", audit.program,
                    f"collective #{entry.index} (collective-permute) "
                    f"source_target_pairs {entry.pairs} are not a "
                    "permutation (duplicated source or target)",
                ))
    # order pin against the linearized schedule
    first: Dict[str, int] = {}
    for entry in schedule:
        first.setdefault(entry.kind, entry.index)
    for late, early, why in ORDER_PINS.get(audit.strategy, ()):
        if late not in first:
            continue
        early_first = min((first[k] for k in early if k in first),
                          default=None)
        if early_first is not None and first[late] < early_first:
            findings.append(_finding(
                "COL001", audit.program,
                f"collective schedule reordered: first {late} (#"
                f"{first[late]}) precedes the first "
                f"{'/'.join(early)} (#{early_first}) — {why}",
            ))
    # zero3 carries its own schedule contract on top of the kind pins:
    # per-block prefetch-scoped gathers, no stray gather, handoff chain
    if audit.strategy == "zero3":
        findings.extend(_check_zero3_prefetch(audit))
    # the pinned kind fingerprint (missing/forbidden kinds) is equally an
    # order-contract violation: an absent sync or a foreign collective
    from tpu_ddp.analysis.explain import check_fingerprint

    fp = check_fingerprint(audit.anatomy, audit.strategy)
    if fp.get("ok") is False:
        for miss in fp["missing"]:
            findings.append(_finding(
                "COL001", audit.program,
                f"pinned fingerprint: required collective family "
                f"missing: {miss}",
            ))
        for extra in fp["unexpected"]:
            findings.append(_finding(
                "COL001", audit.program,
                f"pinned fingerprint: forbidden collective kind present: "
                f"{extra}",
            ))
    return findings


# -- XFR001: host transfers ----------------------------------------------

_CC_TARGET_RE = re.compile(r'custom_call_target="([^"]+)"')
_HOST_OP_RE = re.compile(r"[\]})] (infeed|outfeed)(?:-start)?\(")
_HOSTISH = ("callback", "host", "infeed", "outfeed")


def check_host_transfers(audit: ProgramAudit,
                         cfg: LintConfig) -> List[LintFinding]:
    del cfg
    findings: List[LintFinding] = []
    for eqn in iter_jaxpr_eqns(audit.jaxpr):
        if eqn.primitive.name in CALLBACK_PRIMS:
            findings.append(_finding(
                "XFR001", audit.program,
                f"host callback primitive '{eqn.primitive.name}' inside "
                "the compiled step — a device->host round trip per step",
            ))
    for line in audit.hlo_text.splitlines():
        m = _HOST_OP_RE.search(line)
        if m:
            findings.append(_finding(
                "XFR001", audit.program,
                f"'{m.group(1)}' op in the optimized HLO — host "
                "transfer inside the step",
            ))
            continue
        m = _CC_TARGET_RE.search(line)
        if m and any(h in m.group(1).lower() for h in _HOSTISH):
            findings.append(_finding(
                "XFR001", audit.program,
                f"host custom-call '{m.group(1)}' in the optimized HLO",
            ))
    return findings


#: the program-tier rules, in report order
PROGRAM_CHECKS = (check_donation, check_dtype_widening, check_replication,
                  check_collective_order, check_host_transfers)


def lint_program(step, state, batch, mesh, *, strategy: str = "dp",
                 compute_dtype: str = "float32", cache_key: Any = None,
                 program: Optional[str] = None,
                 config: Optional[LintConfig] = None,
                 model_name: str = "unknown",
                 ) -> Tuple[List[LintFinding], ProgramAudit]:
    """Run every program-tier rule over one step program. The unit the
    CLI, the Trainer preflight, and the injected-violation tests call."""
    cfg = config or LintConfig()
    audit = audit_program(step, state, batch, mesh, strategy=strategy,
                          compute_dtype=compute_dtype, cache_key=cache_key,
                          program=program, model_name=model_name)
    findings: List[LintFinding] = []
    for check in PROGRAM_CHECKS:
        findings.extend(check(audit, cfg))
    return findings, audit


def lint_strategy(strategy: str, *, config: Optional[LintConfig] = None,
                  **prog_kwargs) -> Tuple[List[LintFinding], ProgramAudit]:
    """Lint one strategy's abstract program (the exact step the product
    trains with, via ``build_abstract_step`` + the shared compile cache —
    same cache key as ``tpu-ddp analyze``, so a lint after an analyze is
    free). Accepts every ``prepare_strategy_program`` keyword."""
    from tpu_ddp.analysis.explain import prepare_strategy_program

    prog = prepare_strategy_program(strategy, **prog_kwargs)
    return lint_program(
        prog.step, prog.state, prog.batch, prog.mesh,
        strategy=prog.strategy, compute_dtype=prog.compute_dtype,
        cache_key=prog.cache_key, config=config,
        model_name=prog.model_name,
    )


# -- KRN001: fused-kernel capability tier ---------------------------------

def lint_kernels(enabled: bool, *, backend: Any = "auto",
                 program: str = "kernels") -> List[LintFinding]:
    """KRN001: audit the fused Pallas kernel switch against the
    backend's actual capability. ``enabled`` is the config's
    ``kernels`` switch; ``backend`` is ``tpu_ddp.ops.pallas_backend()``
    (probed when left at ``"auto"``). A switch that is on where no
    Pallas lowering exists fails closed — one error per strategy-level
    kernel, naming the kernel AND the jnp reference it silently falls
    back to, so an operator never believes a kernel ran that didn't."""
    if not enabled:
        return []
    from tpu_ddp.ops import KERNELS, pallas_backend

    if backend == "auto":
        backend = pallas_backend()
    if backend is not None:
        return []
    findings: List[LintFinding] = []
    for name in sorted(KERNELS):
        entry = KERNELS[name]
        if not entry["strategies"]:
            continue  # model-level kernels are not behind this switch
        findings.append(_finding(
            "KRN001", program,
            f"kernel switch is ON but this backend has no Pallas "
            f"lowering: '{name}' will NOT run — the step silently "
            f"takes its XLA fallback ({entry['reference']})",
        ))
    return findings


# -- RCP001: AST tier -----------------------------------------------------

#: CANONICAL module prefixes whose calls bake a different value into
#: every trace (local names are resolved through the module's imports
#: first, so jax.random — keyed, deterministic — never matches even when
#: imported as ``from jax import random``)
_NONDETERMINISTIC = (
    "time.time", "time.monotonic", "time.perf_counter",
    "datetime.datetime.now", "datetime.datetime.utcnow",
    "numpy.random", "random.",
)


def _import_map(tree) -> Dict[str, str]:
    """local name -> canonical dotted module for every import in the
    module (``from jax import random`` -> {"random": "jax.random"})."""
    out: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.asname:  # `import a.b as c` binds c -> a.b
                    out[alias.asname] = alias.name
                else:  # `import a.b` binds the TOP name a -> a
                    head = alias.name.split(".")[0]
                    out[head] = head
        elif isinstance(node, ast.ImportFrom) and node.module:
            for alias in node.names:
                out[alias.asname or alias.name] = \
                    f"{node.module}.{alias.name}"
    return out


def _canonical(dotted: str, imports: Dict[str, str]) -> str:
    head, _, rest = dotted.partition(".")
    full = imports.get(head, head)
    return f"{full}.{rest}" if rest else full


def _is_nondeterministic(name: str) -> bool:
    for p in _NONDETERMINISTIC:
        if p.endswith("."):  # whole-module prefix (stdlib random)
            if name.startswith(p) or name == p[:-1]:
                return True
        elif name == p or name.startswith(p + "."):
            return True
    return False


def _dotted(node) -> str:
    """'jax.jit' for an Attribute/Name chain, '' otherwise."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _is_jit_expr(node) -> bool:
    """The expression produces a fresh jit wrapper: ``jax.jit(...)`` /
    ``jit(...)`` / ``pmap(...)`` or ``functools.partial(jax.jit, ...)``."""
    if not isinstance(node, ast.Call):
        return False
    name = _dotted(node.func)
    if name in ("jax.jit", "jit", "jax.pmap", "pmap"):
        return True
    if name in ("functools.partial", "partial") and node.args:
        return _dotted(node.args[0]) in ("jax.jit", "jit",
                                         "jax.pmap", "pmap")
    return False


def _mutable_default(node) -> bool:
    if isinstance(node, (ast.List, ast.Dict, ast.Set,
                         ast.ListComp, ast.DictComp, ast.SetComp)):
        return True
    if isinstance(node, ast.Call):
        return _dotted(node.func) in ("dict", "list", "set")
    return False


def lint_source_text(text: str, path: str = "<source>",
                     program: str = "source") -> List[LintFinding]:
    """RCP001 over one module's source. Three concrete hazards:
    jit-in-loop (a fresh wrapper per iteration defeats the jit cache —
    every call recompiles), mutable (unhashable) defaults on jitted
    functions (poisons static-arg hashing), and wall-clock / np.random
    calls inside the step factories (a different trace-time constant per
    process is a silent cross-host program divergence)."""
    try:
        tree = ast.parse(text, filename=path)
    except SyntaxError as e:
        return [_finding("RCP001", program,
                         f"syntax error prevents the AST audit: {e}",
                         location=f"{path}:{e.lineno or 0}")]
    findings: List[LintFinding] = []
    fname = os.path.basename(path)
    imports = _import_map(tree)

    def visit(node, loop_depth: int, in_factory: bool):
        if isinstance(node, (ast.For, ast.While)):
            for child in ast.iter_child_nodes(node):
                visit(child, loop_depth + 1, in_factory)
            return
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for deco in node.decorator_list:
                if (_is_jit_expr(deco)
                        or _dotted(deco) in ("jax.jit", "jit")):
                    for d in (node.args.defaults
                              + [d for d in node.args.kw_defaults if d]):
                        if _mutable_default(d):
                            findings.append(_finding(
                                "RCP001", program,
                                f"jitted function '{node.name}' has a "
                                "mutable (unhashable) default argument",
                                location=f"{fname}:{node.lineno}"))
            factory = in_factory or node.name.startswith(("make_", "build_"))
            # a new function scope resets the loop context (a jit built
            # once inside a function that is ITSELF called in a loop is
            # the factory idiom, not the hazard)
            for child in ast.iter_child_nodes(node):
                visit(child, 0, factory)
            return
        if isinstance(node, ast.Call):
            if _is_jit_expr(node) and loop_depth > 0:
                findings.append(_finding(
                    "RCP001", program,
                    "jax.jit built inside a loop body — a fresh wrapper "
                    "per iteration recompiles every call",
                    location=f"{fname}:{node.lineno}"))
            if in_factory:
                name = _canonical(_dotted(node.func), imports)
                if _is_nondeterministic(name):
                    findings.append(_finding(
                        "RCP001", program,
                        f"'{name}' inside a step factory bakes a "
                        "nondeterministic trace-time constant into the "
                        "program (recompiles / cross-host divergence)",
                        location=f"{fname}:{node.lineno}"))
        for child in ast.iter_child_nodes(node):
            visit(child, loop_depth, in_factory)

    visit(tree, 0, False)
    return findings


def lint_source_tree(root: Optional[str] = None) -> List[LintFinding]:
    """RCP001 over every ``.py`` under ``root`` (default: the installed
    ``tpu_ddp`` package)."""
    if root is None:
        import tpu_ddp

        root = os.path.dirname(tpu_ddp.__file__)
    findings: List[LintFinding] = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for name in sorted(filenames):
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            with open(path) as f:
                text = f.read()
            rel = os.path.relpath(path, root)
            file_findings = lint_source_text(text, path=path)
            for fd in file_findings:
                fd.location = fd.location.replace(name, rel, 1)
            findings.extend(file_findings)
    return findings


# -- artifact + CLI -------------------------------------------------------

def rule_counts(findings: Sequence[LintFinding]) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for f in findings:
        out[f.rule] = out.get(f.rule, 0) + 1
    return out


def _program_record(findings: List[LintFinding], audit: ProgramAudit) -> dict:
    """One program's artifact record: findings as exact-gated per-rule
    counts (``tpu-ddp bench compare`` treats a count increase like an
    extra collective) plus the inventory/program-order baseline."""
    return {
        "strategy": audit.program,
        "model": audit.anatomy.model,
        "compute_dtype": audit.compute_dtype,
        "rule_counts": rule_counts(findings),
        "findings": [f.to_json() for f in findings],
        "inventory": audit.anatomy.inventory(),
        "program_order": audit.anatomy.program_order,
        "hlo_ops": audit.anatomy.hlo_ops,
    }


def render_findings(program: str, findings: Sequence[LintFinding],
                    detail: str = "") -> str:
    if not findings:
        return f"tpu-ddp lint: {program}{detail}: clean"
    lines = [f"tpu-ddp lint: {program}{detail}: "
             f"{len(findings)} finding(s)"]
    lines += [f.render() for f in findings]
    return "\n".join(lines)


def main(argv: Optional[Sequence[str]] = None) -> int:
    """``tpu-ddp lint [--strategy all] [--json out.json] ...`` — exit 0
    clean, 1 on any error-severity finding, 2 on usage/env errors."""
    import argparse

    from tpu_ddp.analysis.explain import STRATEGIES

    ap = argparse.ArgumentParser(
        prog="tpu-ddp lint",
        description="static sharding / donation / numerics verifier over "
                    "every strategy's compiled step (docs/lint.md)",
    )
    ap.add_argument("--strategy", default="all",
                    help=f"one of {', '.join(STRATEGIES)}, or 'all' "
                         "(default: all)")
    ap.add_argument("--model", default=None,
                    help="zoo model name (default: tiny per-family model)")
    ap.add_argument("--batch-size", type=int, default=8,
                    help="per-shard batch")
    ap.add_argument("--compute-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="bfloat16 arms the DTY001 widening audit")
    ap.add_argument("--json", default=None,
                    help="write the machine artifact here (per-rule "
                         "counts gate through `tpu-ddp bench compare`)")
    ap.add_argument("--no-source", action="store_true",
                    help="skip the RCP001 AST tier over tpu_ddp/")
    ap.add_argument("--source-root", default=None,
                    help="RCP001 root (default: the tpu_ddp package)")
    ap.add_argument("--kernels", action="store_true",
                    help="audit the fused Pallas kernel switch (KRN001: "
                         "fails closed where no Pallas lowering exists, "
                         "naming each skipped kernel and its fallback)")
    args = ap.parse_args(list(argv) if argv is not None else None)

    strategies = (list(STRATEGIES) if args.strategy == "all"
                  else [args.strategy])
    programs: Dict[str, dict] = {}
    n_errors = 0
    try:
        for strategy in strategies:
            findings, audit = lint_strategy(
                strategy, model_name=args.model,
                per_shard_batch=args.batch_size,
                compute_dtype=args.compute_dtype,
            )
            n_errors += sum(1 for f in findings if f.severity == "error")
            programs[strategy] = _program_record(findings, audit)
            print(render_findings(
                strategy, findings,
                detail=(f" ({audit.anatomy.model}, "
                        f"{audit.device_kind} x{audit.n_devices})")),
                flush=True)
        if not args.no_source:
            src = lint_source_tree(args.source_root)
            n_errors += sum(1 for f in src if f.severity == "error")
            programs["source"] = {
                "strategy": "source",
                "rule_counts": rule_counts(src),
                "findings": [f.to_json() for f in src],
            }
            print(render_findings("source (RCP001 AST tier)", src),
                  flush=True)
        if args.kernels:
            krn = lint_kernels(True)
            n_errors += sum(1 for f in krn if f.severity == "error")
            programs["kernels"] = {
                "strategy": "kernels",
                "rule_counts": rule_counts(krn),
                "findings": [f.to_json() for f in krn],
            }
            print(render_findings("kernels (KRN001 capability tier)",
                                  krn), flush=True)
    except (FileNotFoundError, ValueError) as e:
        print(f"tpu-ddp lint: {e}", flush=True)
        return 2
    if args.json:
        from tpu_ddp.telemetry.provenance import artifact_provenance

        with open(args.json, "w") as f:
            json.dump({
                "lint_schema_version": LINT_SCHEMA_VERSION,
                "programs": programs,
                # commit identity + a stable series key, so archived
                # lint artifacts trend per config across commits
                "provenance": artifact_provenance(
                    descriptor={"artifact": "lint",
                                "strategies": sorted(programs),
                                "compute_dtype": args.compute_dtype},
                ),
            }, f, indent=1)
        print(f"tpu-ddp lint: wrote {args.json} "
              f"({len(programs)} programs)", flush=True)
    if n_errors:
        print(f"tpu-ddp lint: {n_errors} error(s)", flush=True)
        return 1
    print("tpu-ddp lint: all programs clean", flush=True)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
