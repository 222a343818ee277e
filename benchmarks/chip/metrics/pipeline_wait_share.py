"""Share of the workers' time spent blocked in pipeline boundary downloads
(forward activations and backward gradients from the neighbouring stage),
from the program's wall-clock spans: the downloads' seconds over workers x
window, in percent."""


def read(run):
    if run.spans is None:
        return None
    waits = sum(e - s for s, e, span in run.spans
                if span.op == "download" and span.phase in ("fwd", "bwd"))
    return 100.0 * waits / (run.n_workers * run.window_s)
