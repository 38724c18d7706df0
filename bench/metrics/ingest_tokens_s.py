"""ingest_tokens_s: real (unpadded) tokens of the queries completed in the
window, over the window's seconds."""


def read(run):
    return run.summary.get("ingest_tokens_s")
