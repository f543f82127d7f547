"""The comparison that decides ``correct``, rehearsed on the host CPU at a
tiny size through the harness's own drive loop: it passes the program as it
is, fails the reference put in the program's place at a lower precision
than the configuration states, and fails a run whose timed path is broken
underneath."""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, ".."))
sys.path.insert(0, os.path.join(HERE, "..", "..", "src"))

from tiny import TINY_LIMITS, tiny_cell  # noqa: E402

SEED = 2 ** 31 + 405
SECONDS = 3.0


def _session(fault=None):
    from harness.serve import Session, Spans
    cell = tiny_cell()
    sess = Session(cell.config, cell.mix, SEED, SECONDS, Spans(False))
    sess.warm_up()
    if fault is not None:
        fault(sess)
    rec = sess.window(SECONDS)
    assert len(rec["batches"]) >= 4 and rec["votes_folded"] > 0
    return sess, rec


@pytest.fixture(scope="module")
def served():
    from harness import check
    sess, rec = _session()
    final = check.program_final(sess)
    return check.Evidence(sess, SEED), final, rec, sess


def test_program_passes(served):
    from harness import check
    ev, final, rec, sess = served
    nums = check.numbers(ev, final)
    ok, shown = check.verdict(nums, TINY_LIMITS)
    assert ok, shown
    assert nums["fold_mismatch"] == 0
    # the drive loop: lagged votes folded out of order, counters agree
    assert rec["votes_folded"] > 0
    assert final["stats"]["n_routed"] == sum(c.ids.size for c in sess.calls)
    delivered = [int(r) for d in sess.log if hasattr(d, "y") for r in d.ids]
    assert delivered != sorted(delivered)
    assert final["stats"]["n_folded"] == len(delivered)


@pytest.mark.parametrize("enc_mode,kernel_mode,fails", [
    ("int8", "highest", "enc_err"),
    ("highest", "high", "sgld_err"),
])
def test_control_at_lower_precision_fails(served, enc_mode, kernel_mode,
                                          fails):
    from harness import check
    ev = served[0]
    nums = check.control_numbers(ev, enc_mode, kernel_mode)
    ok, shown = check.verdict(nums, TINY_LIMITS)
    assert not ok and shown[fails]["value"] > TINY_LIMITS[fails], shown


def _state_unchanged(sess):
    import jax.numpy as jnp
    svc = sess.svc
    route = svc.route_stream

    def kept(x, prefs=None):
        th = (jnp.copy(svc.state.theta1), jnp.copy(svc.state.theta2))
        out = route(x, prefs=prefs)
        svc.state = svc.state._replace(theta1=th[0], theta2=th[1])
        return out
    svc.route_stream = kept


def _fold_left_out(sess):
    sess.svc.feedback_stream = lambda tickets, y: np.int32(0)


def _half_batch(sess):
    svc = sess.svc
    embed = svc.embed

    def half(tokens, mask):
        x = embed(tokens, mask)
        n = x.shape[0] // 2
        return x.at[n:].set(x[:x.shape[0] - n])
    svc.embed = half


def _answer_altered(sess):
    svc = sess.svc
    route = svc.route_stream
    k = len(sess.pool.names)

    def altered(x, prefs=None):
        a1, a2, t = route(x, prefs=prefs)
        return a1.at[0].set((a1[0] + 1) % k), a2, t
    svc.route_stream = altered


@pytest.mark.parametrize("fault", [_state_unchanged, _fold_left_out,
                                   _half_batch, _answer_altered],
                         ids=["state_unchanged", "fold_left_out",
                              "half_batch", "answer_altered"])
def test_broken_timed_path_is_not_correct(fault):
    from harness import check
    sess, _ = _session(fault)
    ok, shown, _ = check.judge(sess, SEED, TINY_LIMITS)
    assert not ok, shown
