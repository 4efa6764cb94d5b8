"""MiB of RAG arena slab uploaded to the device per round: the change of
the ``obs`` counter ``retrieval.upload_bytes`` over the window, over
the rounds in it.

The counters hold only what changed in the window, so an absent
``retrieval.upload_bytes`` means either nothing was uploaded or the
program counts no uploads. ``fl.local_steps``, counted by the same
instrumentation and in every round, tells the two apart: with it the
reading is 0.0, without it None."""


def read(ctx):
    nbytes = ctx.counters.get("retrieval.upload_bytes")
    if nbytes is None:
        return 0.0 if "fl.local_steps" in ctx.counters else None
    return nbytes / 2**20 / len(ctx.rounds)
