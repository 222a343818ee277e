"""Seconds of gradient synchronisation per step: the program's own sync
wall time of each (stage, replica) worker, the longest per step, averaged
over the window's steps (EngineResult trace metadata ``step_syncs``)."""


def read(run):
    if not run.step_syncs:
        return None
    return sum(run.step_syncs) / len(run.step_syncs)
