"""Training 'traffic': a ring of distinct batches made from the seed. The
train runner stages them on the device during set-up and walks the ring."""

from .. import data


def batches(cell, seed):
    tr = cell["traffic"]
    program = data.module("programs", cell["config"]["program"])
    return program.batches(
        cell["config"], tr["batch"], tr["positions"], seed, tr["ring"]
    )
