"""Device time under a program scope that is no registered op and no phase
(``core.dispatch.program_scope``: a part of the model, ``mtp``), beside
``scope_reduce.py`` (which only a ``benchmark`` PR may change) and over its
join: an instruction's ``op_name`` path, its own or its computation's
root's, else the nearest phase-naming neighbour's by data flow, as
``scope_reduce.rows`` takes it. A path holds the scope where one of its
components, the primitive left out, is that name under any transformations
(``forward/jvp(mtp)/rms_norm/mul``,
``backward/transpose(jvp(mtp))/rms_norm/reduce_sum``)."""
from benchmark import scope_reduce


def holds(op_name, scope):
    return bool(op_name) and any(
        name == scope for name, _ in scope_reduce.scopes(op_name)[:-1])


def instructions_under(hlo_text, scope):
    """The instructions of a compiled module whose path holds ``scope``."""
    lines, roots = scope_reduce.instructions(hlo_text)
    paths = {name: scope_reduce.op_name_of(name, lines, roots)
             for name in lines}
    operands, users = scope_reduce.data_flow(lines)
    out = set()
    for name, path in paths.items():
        if path is None:
            path = scope_reduce.nearest_path(name, users, paths) or \
                scope_reduce.nearest_path(name, operands, paths)
        if holds(path, scope):
            out.add(name)
    return out


def scope_ms(window, scope):
    """Device ms a step, self time, of the traced window's ops under
    ``scope``, forward and backward, first device: 0.0 where the compiled
    step holds no such op, None where the program names no phase (nothing
    of it can be read)."""
    table, _ = scope_reduce.of_window(window)
    if table is None:
        return None
    if scope not in window.compiled_text:
        return 0.0
    under = instructions_under(window.compiled_text, scope)
    return sum(row.ms for row in table if row.instruction in under)
