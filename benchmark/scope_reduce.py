"""From the names the program gives its work to device time by phase, by
program op and by kernel: pure functions over the compiled step's HLO text and
a trace's device ops, checked on ``tests``' recorded fixture.

The join. The TPU's trace names a device op by its HLO instruction
(``trace_reduce.label``: ``"fusion.1604 fusion"``), and the compiled text
gives every instruction its ``metadata={op_name="..."}``, the path of program
scopes under which the instruction was traced:

    jit(pure)/forward/jvp(sdpa)/flash_fwd_causal/pallas_call
    jit(pure)/backward/transpose(jvp(linear))/dot_general
    jit(pure)/backward/transpose(forward)/jvp(sdpa)/flash_bwd_dq_causal/pallas_call
    jit(pure)/optimizer/mul

The program sets them (PR 24): ``TrainStep`` opens ``forward``, ``backward``,
``optimizer`` and ``grad_exchange``; ``core.dispatch.apply`` opens the
registered op's name inside the function ``jax.vjp`` differentiates, so jax
carries it to the backward ops as ``transpose(jvp(<op>))``; every
``pallas_call`` has a ``name=``, which is both a scope and the instruction's
own name (``%flash_fwd_causal.12``). A path's components are scopes, each
possibly wrapped by the transformations it was traced under (``jvp(..)``,
``transpose(..)``, ``jit(..)``); the last component is the primitive.

The rules, as settled on cell gpt2s_pretrain_1k's HLO (jax 0.9.0):

- **phase**: ``optimizer`` if a scope is named so, else ``grad_exchange``
  likewise, else ``backward`` if a scope is named ``backward`` or any
  component is wrapped in ``transpose(``, else ``forward`` if a scope is named
  so, else none. ``backward`` goes before ``forward`` because a
  ``custom_vjp``'s backward rule (the flash, layer-norm and CE kernels') is
  traced as ``backward/transpose(forward)/jvp(sdpa)/...``: the enclosing
  forward scope comes along inside the ``transpose``.
- **program op**: the innermost scope that is a registered op name, the
  primitive left out (the primitive ``transpose`` of a ``linear``'s backward
  is not the op ``transpose``) and jax's own jitted functions too
  (``jvp(cross_entropy_hard)/jit(log_softmax)/sub`` is the dense path of the
  op ``cross_entropy_hard`` calling ``jax.nn.log_softmax``, not the op
  ``log_softmax``).
- **kernel**: a Mosaic call's instruction name up to its ``.N``.
- an instruction without metadata of its own takes its computation's root's
  (a fusion). One that still has none is the compiler's own data movement
  (the start and done of an asynchronous copy or slice between memory
  spaces, a scalar copy: 1.7% of cell 1's busy time and 3.1% of cell
  bert_base_mlm_512's, nearly all in the ``-done`` waits): it takes the path
  of the nearest instruction that names a phase, through its users first (the
  op that waits for the data), then through its operands (the op whose
  result it moves; a copy into the step's outputs has no user).
- an op of the trace whose instruction the text does not hold, or whose
  path, its own or so taken, names no phase, is **unscoped**: counted, not
  dropped.

Time is self time: every nanosecond in which the device runs an op goes to
the innermost op running then (a ``while`` holds its body's ops), so the
phases and the unscoped rest add up to the device's busy time exactly.
"""
import dataclasses
import re

from benchmark import trace_reduce

PHASES = ("forward", "backward", "optimizer", "grad_exchange")
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+) = ")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) \(.*\{\s*$")
_OP_NAME = re.compile(r'metadata=\{[^{}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
_REFERENCE = re.compile(r"%([\w.\-]+)")


def instructions(hlo_text):
    """({instruction: its line}, {computation: its root instruction}) of
    every computation in a compiled module's text. Instruction names are
    unique in a module."""
    lines, roots, computation = {}, {}, None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            lines[m.group(2)] = line
            if m.group(1) and computation:
                roots[computation] = m.group(2)
            continue
        m = _COMPUTATION.match(line)
        if m:
            computation = m.group(1)
    return lines, roots


def op_name_of(instruction, lines, roots):
    """The instruction's ``op_name``: its own metadata, else that of the
    root of the computation it calls; None where neither has one or the text
    does not hold the instruction."""
    seen = set()
    while instruction in lines and instruction not in seen:
        seen.add(instruction)
        line = lines[instruction]
        m = _OP_NAME.search(line)
        if m:
            return m.group(1)
        called = _CALLS.search(line)
        instruction = roots.get(called.group(1)) if called else None
    return None


def data_flow(lines):
    """({instruction: its operands}, {instruction: its users}), from the
    ``%names`` on each instruction's line that are instructions."""
    operands, users = {}, {}
    for name, line in lines.items():
        operands[name] = [o for o in _REFERENCE.findall(
            line.partition(" = ")[2]) if o in lines and o != name]
        for o in operands[name]:
            users.setdefault(o, []).append(name)
    return operands, users


def nearest_path(instruction, graph, paths):
    """The path of the nearest instruction, breadth first along ``graph``
    (``data_flow``'s users or operands), that names a phase; ``paths`` is
    {instruction: its ``op_name``}."""
    seen, frontier = {instruction}, [instruction]
    while frontier:
        reached = []
        for at in frontier:
            for other in graph.get(at, ()):
                if other in seen:
                    continue
                seen.add(other)
                if phase_of(paths[other]):
                    return paths[other]
                reached.append(other)
        frontier = reached
    return None


def scopes(op_name):
    """[(scope, transformations round it)] of a path's components, the
    outermost first: ``transpose(jvp(sdpa))`` gives ``("sdpa", ("transpose",
    "jvp"))``."""
    out = []
    for part in op_name.split("/"):
        wraps = []
        m = _WRAPPED.match(part)
        while m:
            wraps.append(m.group(1))
            part = m.group(2)
            m = _WRAPPED.match(part)
        out.append((part, tuple(wraps)))
    return out


def phase_of(op_name):
    """One of PHASES, or None: the rule of the module's docstring."""
    if not op_name:
        return None
    parts = scopes(op_name)
    names = {name for name, _ in parts[:-1]}
    for phase in ("optimizer", "grad_exchange"):
        if phase in names:
            return phase
    if "backward" in names or any("transpose" in w for _, w in parts):
        return "backward"
    return "forward" if "forward" in names else None


def program_op_of(op_name, registered):
    """The innermost scope of the path that ``registered`` holds, its
    primitive and jax's own ``jit(..)`` functions left out; None where there
    is none."""
    if not op_name:
        return None
    for name, wraps in reversed(scopes(op_name)[:-1]):
        if name in registered and "jit" not in wraps:
            return name
    return None


def kernel_of(label):
    """A Mosaic call's kernel name, the instruction's up to its ``.N``; None
    for any other op. ``label`` is the trace's (``trace_reduce.label``)."""
    if not trace_reduce.is_mosaic(label):
        return None
    return re.sub(r"\.\d+$", "", label.split(" ")[0])


def self_times(events):
    """{label: [nanoseconds, calls]}: each event's duration less what the
    events nested in it cover, summed by label."""
    out, stack = {}, []   # stack of [label, end, own nanoseconds]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            label, _, own = stack.pop()
            entry = out.setdefault(label, [0, 0])
            entry[0] += own
            entry[1] += 1

    for label, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            end = min(end, stack[-1][1])
            stack[-1][2] -= end - start
        stack.append([label, end, end - start])
    close(float("inf"))
    return out


@dataclasses.dataclass
class Row:
    label: str          # the trace's: "<instruction> <opcode>"
    phase: str          # or None
    program_op: str     # or None
    kernel: str         # or None
    ms: float           # device milliseconds a step, self time
    calls: float        # executions a step
    by_data_flow: bool  # the path is a neighbour's: the op has none

    @property
    def instruction(self):
        return self.label.split(" ")[0]


def rows(lines, roots, events, steps, registered):
    """One Row per distinct op of ``events`` (one device's trace ops over
    ``steps`` steps), the longest first; ``lines`` and ``roots`` are
    ``instructions``' of the compiled text."""
    paths = {name: op_name_of(name, lines, roots) for name in lines}
    # a program that names no phase (one from before PR 24) has no
    # neighbour to take a path from: its ops stay unscoped
    operands, users = data_flow(lines) \
        if any(phase_of(path) for path in paths.values()) else ({}, {})
    out = []
    for label, (ns, calls) in self_times(events).items():
        instruction = label.split(" ")[0]
        path = paths.get(instruction)
        moved = path is None and instruction in operands
        if moved:
            path = nearest_path(instruction, users, paths) or \
                nearest_path(instruction, operands, paths)
        out.append(Row(label, phase_of(path),
                       program_op_of(path, registered), kernel_of(label),
                       ns / steps / 1e6, calls / steps, moved))
    return sorted(out, key=lambda r: -r.ms)


def by(table, key):
    """{value of ``key(row)``: ms a step} over a table of Rows."""
    out = {}
    for row in table:
        out[key(row)] = out.get(key(row), 0.0) + row.ms
    return out


def of_window(window):
    """(table, instruction lines) of a traced window's first device; the
    registered op names are the program's (``paddle_tpu.ops.OP_REGISTRY``).
    (None, lines) where no op of the step is under a ``forward`` scope, as in
    a program from before PR 24 (jax's own ``transpose(`` still marks its
    backward ops): the program does not name its work, and the readers have
    nothing to read. Nine readers ask for the same window, so the answer is
    kept on the window."""
    kept = vars(window)
    if "scope_table" not in kept:
        from paddle_tpu.ops import OP_REGISTRY

        trace = window.trace
        lines, roots = instructions(window.compiled_text)
        table = rows(lines, roots, next(iter(trace.ops.values())),
                     trace.steps, set(OP_REGISTRY))
        if not any(r.phase == "forward" for r in table):
            table = None
        kept["scope_table"] = table, lines
    return kept["scope_table"]


def phase_ms(window, *phases):
    table, _ = of_window(window)
    if table is None:
        return None
    return sum(r.ms for r in table if r.phase in phases)


def program_op_ms(window, *ops):
    """Forward and backward together: the op's name is on both."""
    table, _ = of_window(window)
    if table is None:
        return None
    return sum(r.ms for r in table if r.program_op in ops)
