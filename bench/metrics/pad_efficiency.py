"""Batch forming: live rows over padded rows of the formed batches, in %."""


def read(run):
    b = run.rec["batches"]
    pad = sum(bucket for _, bucket in b)
    return 100.0 * sum(n for n, _ in b) / pad if pad else None
