"""How much a fleet's chips run at the same time: 100 x the sum over the
N chips of each chip's busy time (union of its "XLA Ops") over N x the
time in which any chip is busy, in the trace window. It reads 100/N when
the chips run strictly one after another, and 100 when they always run
together."""
import numpy as np

from bench.lib.trace import busy_intervals, busy_s


def read(ctx):
    tr = ctx.trace
    if tr is None or len(tr.devices) < 2:
        return None
    per_chip = [busy_intervals(d) for d in tr.devices]
    total = sum(busy_s(iv, tr.window) for iv in per_chip)
    anyone = busy_s(np.concatenate([np.reshape(iv, (-1, 2))
                                    for iv in per_chip]), tr.window)
    if anyone <= 0:
        return None
    return 100.0 * total / (len(tr.devices) * anyone)
