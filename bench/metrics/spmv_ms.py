"""``spmv_ms``: device milliseconds per SpMV, from the trace: the device
time of the program that runs the SpMV over its executions in the window.

The restarted engine calls the SpMV as one jitted program with the layout
as an argument, ``kernels.engine._container_spmv``, which the trace names
``jit__container_spmv``."""

PROGRAM = "_container_spmv"


def per_call_seconds(outcome):
    t = outcome.trace
    hit = t.program(PROGRAM) if t is not None else None
    if not hit or not hit[0]:
        return None
    count, seconds = hit
    return seconds / count


def read(outcome, peaks):
    s = per_call_seconds(outcome)
    return None if s is None else 1e3 * s
