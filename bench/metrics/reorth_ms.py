"""``reorth_ms``: device milliseconds per Lanczos step of the restarted
engine's full reorthogonalisation, from the trace: the device time of its
jitted ``orth`` program (``core.restarted.restart_kernels``, named
``jit_orth``) over its executions, one per step."""

PROGRAM = "jit_orth"


def read(outcome, peaks):
    t = outcome.trace
    hit = t.program(PROGRAM) if t is not None else None
    if not hit or not hit[0]:
        return None
    count, seconds = hit
    return 1e3 * seconds / count
