"""Share of the window in which the host waits on the local steps: the
``obs`` ``client.loss_read`` spans (the blocking read of each step's
loss, which ends when the step has run on the device), over the
window."""


def read(ctx):
    spans = [d for name, _, d, _ in ctx.spans if name == "client.loss_read"]
    return 100.0 * sum(spans) / ctx.window_s if spans else None
