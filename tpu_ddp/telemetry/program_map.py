"""The program map: which phase and module each device operation belongs to.

A device trace names an operation by its HLO instruction (``fusion.11``): a
compiler-numbered name that says nothing and changes whenever the program
does. What the instruction was built from is in the compiled program's
text, as ``metadata={op_name="jit(shard_step)/tpu_ddp.forward_backward/
transpose(jvp(ResNet))/head/dot_general"}``, and only the program can give
that text. So with telemetry on the ``Trainer`` writes, once per compiled
step program, one record to ``programs-p<host>[.i<k>].jsonl`` in the run
directory (the sink grammar of ``telemetry.sink_file_name``):

    {"type": "program_map", "schema_version": 1,
     "program": "train_step",           # which of the Trainer's callables
     "module": "jit_shard_step",        # as the trace's XLA Modules line has it
     "dispatch": 3,                     # exported after this dispatch
     "export_seconds": 0.41,
     "mixed_fusions": 152,              # fusions holding work of several phases
     "phases": {"forward": 431, ...},   # instructions by phase
     "instructions": {"fusion.11": {"op_name": "...", "opcode": "fusion",
                                    "phase": "backward", "module": "head"}}}

An instruction the compiler made itself has no ``op_name`` (a custom call
it emits for one primitive has only its own name for it, after the path of
the jitted function's call where it sits in one); it takes the phase of the
instruction that uses its result and says ``"inherited": true``.
A ``conditional`` or a ``while`` has the phase ``"control"``, which is none:
a trace shows it for as long as the instructions of the branch or body it
runs, and shows those too, so a sum by phase that counted it would count
their time twice (``phases.CONTROL``).
A fusion takes the phase of the ``op_name`` the compiler left on it; where
the instructions fused into it disagree on the phase (a weight-gradient
convolution with the optimizer's update as its epilogue), it says
``"mixed": true``.

Reading it beside a capture's ``.xplane.pb``: sum the durations of the
``XLA Ops`` events by ``instructions[<event name up to " = ">]["phase"]``
(``chipbench/scopes.py`` does; ``docs/profiling.md`` shows it by hand).

The map is of the executable that runs. jax keys its persistent cache
without the metadata, so a program loaded from a cache that an older tree
wrote carries that tree's scope names: a scope added since reads ``other``
until the cache entry is compiled anew.

No jax import here: reading a map needs the stdlib alone, and the jitted
callable is used through ``lower(...).compile().as_text()`` and
``_cache_size()``.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, Optional

from tpu_ddp.telemetry import phases

PROGRAM_MAP_SCHEMA_VERSION = 1
SINK_PREFIX = "programs"
#: export at the latest after this many dispatches of a program, whatever
#: the jit cache does (a jax without ``_cache_size``)
SETTLED_BY = 3


def build_record(hlo_text: str, *, program: str, dispatch: int = 0) -> dict:
    """The record of one compiled program, from its optimised HLO text
    (``export_seconds`` is the exporter's to add)."""
    from tpu_ddp.analysis.hlo import program_instructions

    module, instructions = program_instructions(hlo_text)
    if not instructions:
        raise ValueError("the compiled program's text holds no instruction")
    table: Dict[str, dict] = {}
    mixed = 0
    for ins in instructions:
        phase, mod = phases.classify(ins["op_name"], ins["opcode"])
        row = {"op_name": ins["op_name"], "opcode": ins["opcode"],
               "phase": phase, "module": mod}
        if phases.is_mixed(ins["op_name"], *ins["body_op_names"]):
            row["mixed"] = True
            mixed += 1
        table[ins["name"]] = row
    _inherit(instructions, table)
    by_phase = {phase: 0 for phase in phases.PHASES}
    for row in table.values():  # ``control`` is counted where there is any
        by_phase[row["phase"]] = by_phase.get(row["phase"], 0) + 1
    return {
        "type": "program_map",
        "schema_version": PROGRAM_MAP_SCHEMA_VERSION,
        "program": program,
        "module": module,
        "dispatch": dispatch,
        "mixed_fusions": mixed,
        "phases": by_phase,
        "instructions": table,
    }


#: opcodes that never run as an operation: nothing to name
_NEVER_RUN = frozenset(("parameter", "constant", "get-tuple-element",
                        "tuple", "bitcast"))


def _inherit(instructions, table) -> None:
    """An instruction the compiler made itself (a layout copy, a prefetch
    pair, a rewritten convolution) has no ``op_name``. It takes the phase
    and module of the first instruction that uses its result, else of its
    first named operand, and is marked ``inherited``: where the data goes
    is where the work belongs."""
    def compilers_own(ins):
        # a kernel the compiler emits itself for one primitive (XLA:TPU's
        # grouped product for ``lax.ragged_dot``) is a custom call that
        # keeps the compiler's name for it, ``ragged-dot-none``, and no path
        # of the program's; inside a jitted function that the compiler
        # inlined it has the path of the call before that name, which says
        # where the function was called and not which scope the work is in
        if ins["opcode"] != "custom-call":
            return False
        last = ins["op_name"].rsplit("/", 1)[-1]
        return "/" not in ins["op_name"] or (
            bool(last) and ins["name"].startswith(last))

    def unnamed(ins):
        if compilers_own(ins):
            return True
        return not ins["op_name"] and ins["opcode"] not in _NEVER_RUN

    def take(row, source):
        if source is not None and source["phase"] not in (
                phases.OTHER, phases.CONTROL):  # a switch hands on no phase
            row.update(phase=source["phase"], module=source["module"],
                       inherited=True)
            return True
        return False

    first_user = {}
    for ins in instructions:
        if compilers_own(ins):  # whatever its call's path made of it
            table[ins["name"]].update(phase=phases.OTHER, module="")
        for operand in ins["operands"]:
            first_user.setdefault(operand, ins["name"])
    # users follow their operands in a scheduled computation: backwards,
    # a chain (copy-start, copy-done, fusion) resolves in one pass
    for ins in reversed(instructions):
        if unnamed(ins) and table[ins["name"]]["phase"] == phases.OTHER:
            take(table[ins["name"]], table.get(first_user.get(ins["name"])))
    for ins in instructions:
        row = table[ins["name"]]
        if unnamed(ins) and row["phase"] == phases.OTHER:
            for operand in ins["operands"]:
                if take(row, table.get(operand)):
                    break


def read_program_maps(path: str) -> list:
    """Every program-map record of one file, in the order written."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            record = json.loads(line)
            if record.get("type") != "program_map":
                continue
            if record.get("schema_version", 0) > PROGRAM_MAP_SCHEMA_VERSION:
                raise ValueError(
                    f"{path}: program map schema_version "
                    f"{record['schema_version']} is newer than this reader "
                    f"({PROGRAM_MAP_SCHEMA_VERSION})")
            out.append(record)
    return out


def newest_program_map_file(run_dir: str,
                            process_index: int = 0) -> Optional[str]:
    """The newest incarnation's map file of one host, None where the run
    wrote none."""
    from tpu_ddp.telemetry import parse_sink_name

    best = None
    for name in os.listdir(run_dir) if os.path.isdir(run_dir) else ():
        parsed = parse_sink_name(name, prefix=SINK_PREFIX)
        if parsed and parsed[1] == process_index and parsed[3] == "jsonl":
            if best is None or parsed[2] > best[0]:
                best = (parsed[2], os.path.join(run_dir, name))
    return best and best[1]


class ProgramMapExporter:
    """Writes the map of each step program the ``Trainer`` built, once, when
    the program has settled.

    ``programs`` maps the run loop's dispatch kind (``"single"``,
    ``"stacked"``) to ``(name, jitted callable)``: the callables the
    ``Trainer`` built, whatever stands in its ``train_step`` attribute by
    then. A jitted step is compiled anew while its arguments still change
    kind (the first call takes the state as initialised, the second the
    state a step returned), so the export waits for the first dispatch that
    leaves the jit cache as large as the one before left it: that dispatch
    ran the steady program. It then lowers the callable on the live
    arguments, which the trace and lowering caches answer, and reads the
    text of the executable that is already there: no backend compile, no
    cache load."""

    def __init__(self, run_dir: str, programs: dict, *,
                 process_index: int = 0, incarnation: int = 0):
        from tpu_ddp.telemetry import sink_file_name

        self.path = os.path.join(run_dir, sink_file_name(
            SINK_PREFIX, process_index, incarnation))
        self._pending = dict(programs)
        self._seen = {kind: [0, None] for kind in programs}
        self.records = []  # what was written: (program, seconds, bytes)
        # this incarnation's file starts empty even if no program settles
        os.makedirs(run_dir, exist_ok=True)
        open(self.path, "w").close()

    @property
    def done(self) -> bool:
        return not self._pending

    def after_dispatch(self, kind: str, *args) -> None:
        """Call after every dispatch of ``kind`` with the arguments the
        NEXT dispatch would take (the state just returned, the batch)."""
        if kind not in self._pending:
            return
        name, fn = self._pending[kind]
        seen = self._seen[kind]
        seen[0] += 1
        try:
            size = fn._cache_size()
        except Exception:
            size = None
        settled = (seen[0] >= SETTLED_BY if size is None
                   else size == seen[1])
        seen[1] = size
        if not settled:
            return
        del self._pending[kind]
        try:
            self._export(name, fn, args, seen[0])
        except Exception as e:  # observability never stops the run
            import logging

            logging.getLogger(__name__).warning(
                "program map of %s not written: %s", name, e)

    def _export(self, name, fn, args, dispatch: int) -> None:
        from tpu_ddp.telemetry.jax_hooks import paused

        t0 = time.perf_counter()
        with paused():
            text = fn.lower(*args).compile().as_text()
        record = build_record(text, program=name, dispatch=dispatch)
        record["export_seconds"] = time.perf_counter() - t0
        line = json.dumps(record) + "\n"
        with open(self.path, "a") as f:
            f.write(line)
        self.records.append((name, record["export_seconds"], len(line)))
