"""Blocking host reads of the device in cohort training, per round: the
change of the ``obs`` counter ``fl.train_syncs`` over the window, over
the rounds in it.

A program that does not count these reads (one that waits on every
local step) has no ``fl.train_syncs`` in its window and reads None; so
does a window without ``fl.local_steps``, in which no client trained."""


def read(ctx):
    syncs = ctx.counters.get("fl.train_syncs")
    if syncs is None or "fl.local_steps" not in ctx.counters:
        return None
    return syncs / len(ctx.rounds)
