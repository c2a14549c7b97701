"""A whole run of a tiny cell on the CPU, the chip check skipped: sound,
it comes out correct; with the timed path broken underneath, not."""
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import testing  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return testing.tiny_root(str(tmp_path_factory.mktemp("tiny")))


def altered_token(eng):
    """A token altered where it is produced: the decode program's greedy
    token of every row is shifted by one."""
    inner = eng._decode

    def decode(*a, **k):
        greedy, lg, *pools = inner(*a, **k)
        return (greedy + 1) % 500, lg, *pools

    eng._decode = decode


def unchanged_state(eng):
    """A step that returns its state unchanged: the decode program's new
    KV rows are dropped, so the pool keeps what it held before."""
    inner = eng._decode

    def decode(p, t, bt, ln, *pools, **k):
        greedy, lg, *_ = inner(p, t, bt, ln, *pools, **k)
        return greedy, lg, *pools

    eng._decode = decode


def half_batch(eng):
    """Half of the batch left out: every odd row of the decode program is
    given the token of the even row before it."""
    inner = eng._decode

    def decode(*a, **k):
        greedy, lg, *pools = inner(*a, **k)
        return greedy.at[1::2].set(greedy[0::2]), lg, *pools

    eng._decode = decode


def skipped_prefix(eng):
    """A prefill chunk computed from the wrong position: every chunk after
    the first starts at position 0."""
    import jax.numpy as jnp
    inner = eng._prefill_chunk_fn

    def chunk(p, t, pos0, *rest):
        return inner(p, t, jnp.zeros_like(pos0), *rest)

    eng._prefill_chunk_fn = chunk


def run_line(root, patch, seed):
    from bench import run
    return json.loads(run.run(testing.args(seed=seed), root,
                              require_tpu=False, patch_engine=patch))


def test_sound_run_is_correct(root):
    out = run_line(root, None, 11)
    assert out["correct"], out["check"]
    assert list(out)[-1] == "check"
    assert out["failed"] == 0 and out["attempted"] > 0
    assert {"output_tok_s", "setup_s"} == set(out["metrics"])


@pytest.mark.parametrize("fault", [altered_token, unchanged_state,
                                   half_batch, skipped_prefix])
def test_broken_path_is_not_correct(root, fault):
    out = run_line(root, fault, 12)
    assert not out["correct"], out["check"]
    assert out["check"]["gap_max"]["value"] > out["check"]["gap_max"]["limit"]


def test_control_fails_the_limit_the_program_meets(root):
    """The float8 control of the reference, at the tiny cell's size, reads
    above the cell's limit where the reference's own greedy tokens read 0:
    over four prompts of twelve served tokens each."""
    import jax.numpy as jnp
    from bench import generator, run
    c = run.load_cell(testing.TINY, root)
    w = c.ref.make_weights(c.sizes, 3)
    rng = np.random.default_rng(0)
    seq_len = -(-generator.max_len(c.mix) // c.ref.Q_BLOCK) * c.ref.Q_BLOCK
    import jax

    @jax.jit
    def logits_at(w, x, i):
        h = c.ref._hidden(w, x, c.sizes, False)
        return c.ref._logits(w, h[i][None], c.sizes, False)[0]

    worst, worst_ctrl = 0.0, 0.0
    for _ in range(4):
        prompt = rng.integers(1, 500, 60).astype(np.int32)
        toks = []
        for _ in range(12):
            s = np.concatenate([prompt, np.asarray(toks, np.int32)])
            x = np.zeros(seq_len, np.int32)
            x[:len(s)] = s
            lg = logits_at(w, jnp.asarray(x), len(s) - 1)
            toks.append(int(jnp.argmax(lg)))
        gaps, ctrl = c.ref.served_gaps(w, c.sizes, prompt, toks, seq_len,
                                       12, control=True)
        worst = max(worst, float(gaps.max()))
        worst_ctrl = max(worst_ctrl, float(ctrl.max()))
    assert worst <= c.limits["gap_max"]
    assert worst_ctrl > c.limits["gap_max"]


def test_traced_run_reports_host_layers(root):
    """A ``--trace 1`` run reports the per-layer metrics it can read (on
    the CPU there is no device plane, so the device ones are left out),
    the traced window, and the same ``correct``."""
    from bench import run
    out = json.loads(run.run(testing.args(seed=13, seconds=3.0, trace=1),
                             root, require_tpu=False))
    assert out["correct"], out["check"]
    assert "pool.preemptions" in out["metrics"]
    assert "output_tok_s" not in out["metrics"]
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
