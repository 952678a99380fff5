"""Per step, the time a collective runs on a device while no other op runs
on it; worst device. Nothing to read where the trace holds no collective."""
LAYER = "sharded step"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def reports(cell):
    return cell["chips"] > 1


def read(window):
    from benchmark import trace_reduce

    trace = window.trace
    if not any(trace_reduce.is_collective(name)
               for events in trace.ops.values() for name, _, _ in events):
        return None
    return max(trace_reduce.exposed_collective_ns(events)
               for events in trace.ops.values()) / trace.steps / 1e6
