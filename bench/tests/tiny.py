"""A tiny cell for host-CPU rehearsals of the harness (not a benchmark
configuration): a real cell's pool, corpus and traffic shape at toy widths
and sizes, with limits set from host-CPU readings at this size."""
import copy
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

# host CPU, this size, with a step size (0.02) at which the data term
# moves the chains far against the float32 rounding of their state, as in
# the cells on the chip; 15 runs on 12 seeds: the program reads enc_err
# 1.8e-7 to 3.7e-7, sgld_err 0.51 to 0.74 and pair_gap 0 against the
# references; the int8 encoder control reads enc_err far above, the "high"
# kernel control sgld_err 6.1 to 14.8, one bf16 pass 3.7e3 to 7.4e3
TINY_LIMITS = {"enc_err": 1e-5, "sgld_err": 3.0, "pair_gap": 1e-6,
               "fold_mismatch": 0}


def tiny_cell(traffic: str = "steady", rate: float = 300.0,
              config: str = "routerbench-distilbert"):
    from harness.cell import Cell
    base = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    cfg = copy.deepcopy(base)
    enc = cfg["encoder"]
    for k, v in (("dim", 32), ("hidden_size", 32), ("n_layers", 1),
                 ("num_hidden_layers", 1), ("n_heads", 2),
                 ("num_attention_heads", 2), ("hidden_dim", 64),
                 ("intermediate_size", 64), ("vocab_size", 2048),
                 ("max_position_embeddings", 8)):
        if k in enc:
            enc[k] = v
    cfg["fgts"].update(horizon=64, sgld_steps=3, sgld_minibatch=16,
                       n_chains=2, sgld_eps=0.02)
    cfg["serving"].update(buckets=[4, 8], feedback_capacity=32)
    cfg["check"] = dict(TINY_LIMITS)
    mix = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    mix["rate"] = rate
    mix["vote_lag_batches"] = [1, 2]
    return Cell(f"tiny.{traffic}", 1, cfg, mix, [], [])
