"""``api_ms``: host milliseconds per request spent in ``repro.eigsh`` outside
the engine's solve (``timings["solve_s"]``): fingerprinting the CSR, the
session-cache lookup, query normalisation and assembling the result.
Taken by the harness's host clock around each call; the mean over the
window's requests."""


def read(outcome, peaks):
    reqs = outcome.requests
    if not reqs:
        return None
    return 1e3 * sum(r.api_s for r in reqs) / len(reqs)
