"""The whole routed step's share of the chip's bf16 peak: the encoder's
forward FLOPs for every request routed in the window (live rows only, at
the configuration's widths, as its encoder module counts them), plus each
formed batch's SGLD refresh (two samples of every chain, ``sgld_steps``
forward and backward potential evaluations) and dueling selection, over
the window and the peak."""
from . import costs


def serve_mfu(run) -> float | None:
    s, rec = run.sess, run.rec
    if not rec["routed"]:
        return None
    fg = s.fgts_cfg
    k = len(s.pool.names)
    step = sum(costs.sgld_kernel_cost(kind, fg.sgld_minibatch, k, s.d,
                                      fg.n_chains)[0]
               for kind in ("fwd", "bwd"))
    flops = s.encoder.flops_per_query(s.enc, s.seq_len) * rec["routed"]
    for n, _ in rec["batches"]:
        flops += 2 * fg.sgld_steps * step + costs.select_flops(n, k, s.d)
    return 100.0 * flops / rec["window_s"] / run.peaks.flops_bf16
