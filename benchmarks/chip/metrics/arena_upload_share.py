"""Share of the window spent uploading RAG arena slabs to the device:
the ``obs`` ``retrieval.upload`` spans (each one waits for its transfer
in a traced run), over the window."""


def read(ctx):
    spans = [d for name, _, d, _ in ctx.spans if name == "retrieval.upload"]
    return 100.0 * sum(spans) / ctx.window_s if spans else None
