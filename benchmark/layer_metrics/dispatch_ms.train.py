"""Host time of one `trainer.update(StagedBatch)` call, median over the
traced window's steps: what the host spends per step before the device
has the work (host clock; the call does not wait for the device)."""

import statistics


def read(obs):
    if not obs.window.dispatch_s:
        return None
    return 1e3 * statistics.median(obs.window.dispatch_s)
