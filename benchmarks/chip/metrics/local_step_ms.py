"""Host wall time of one local training step, in ms: the ``obs`` spans
``client.batch`` (the host batch and its transfers), ``client.dispatch``
(the jitted step's enqueue) and ``client.loss_read`` (the host blocked
on the step's loss), summed, over the change of the ``fl.local_steps``
counter in the window."""

STEP_SPANS = ("client.batch", "client.dispatch", "client.loss_read")


def read(ctx):
    steps = ctx.counters.get("fl.local_steps", 0)
    spans = [d for name, _, d, _ in ctx.spans if name in STEP_SPANS]
    if not spans or steps <= 0:
        return None
    return 1e3 * sum(spans) / steps
