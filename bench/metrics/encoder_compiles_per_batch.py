"""Query encoder: backend compiles (``jax.monitoring``) inside the
``bench.embed`` spans of the window, per formed batch."""


def read(run):
    n = len(run.rec["batches"])
    return run.compiles.get("embed", 0) / n if n else None
