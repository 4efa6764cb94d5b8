"""Share of the window spent building local steps' inputs: the ``obs``
``client.batch`` spans (``batchify`` on the host and the batch's
``jnp.asarray`` transfers), host wall time, over the window."""


def read(ctx):
    spans = [d for name, _, d, _ in ctx.spans if name == "client.batch"]
    return 100.0 * sum(spans) / ctx.window_s if spans else None
