"""``ops/ssd.py``: the chunked scan against the literal per-token
recurrence, values and the gradient of every input, with documents
that end inside a chunk, at a chunk's edge, and after one token."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.ops.ssd import reset_masks, ssd_scan

H, P, N, CHUNK = 4, 8, 16, 8


def recurrence(x, dt, A, B, C, D, segment_ids):
    """``H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T`` with ``H_{t-1}``
    dropped at a document's first token; ``y_t = H_t C_t + D x_t``.
    One ``lax.scan`` over time a sequence, float32."""
    def one_sequence(x, dt, B, C, seg):
        starts = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])

        def step(state, t):
            x_t, dt_t, b_t, c_t, start = t
            state = jnp.where(start, 0.0, state)
            state = jnp.exp(dt_t * A)[:, None, None] * state \
                + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
            return state, state @ c_t + D[:, None] * x_t

        _, y = jax.lax.scan(step, jnp.zeros((H, P, N)),
                            (x, dt, B[:, 0], C[:, 0], starts))
        return y
    return jax.vmap(one_sequence)(x, dt, B, C, segment_ids)


def inputs(rows, s, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    return {"x": f(rows, s, H, P),
            "dt": jax.nn.softplus(f(rows, s, H) - 1.0),
            "A": -jnp.exp(jnp.asarray(rng.uniform(0, 2.5, H), jnp.float32)),
            "B": f(rows, s, 1, N), "C": f(rows, s, 1, N), "D": 1 + f(H)}


def segments(lengths):
    return jnp.asarray([np.repeat(np.arange(len(row)), row)
                        for row in lengths], jnp.int32)


S = 4 * CHUNK
CASES = {
    "no_boundary": [[S]],
    "boundary_inside_a_chunk": [[11, S - 11]],
    "boundary_at_a_chunks_edge": [[2 * CHUNK, 2 * CHUNK]],
    "a_document_of_one_token": [[5, 1, S - 6]],
    "first_and_last_tokens_alone": [[1, S - 2, 1]],
    "several_sequences": [[S], [3, 13, S - 16], [CHUNK, 1, CHUNK - 1,
                                                 2 * CHUNK]],
}


def scalar(fn, weights, args, seg):
    y = fn(args["x"], args["dt"], args["A"], args["B"], args["C"],
           args["D"], seg)
    return (y.astype(jnp.float32) * weights).sum()


@pytest.mark.parametrize("case", sorted(CASES))
def test_chunked_scan_is_the_recurrence_in_float32(case):
    seg = segments(CASES[case])
    args = inputs(len(CASES[case]), S, seed=len(case))
    chunked = lambda *a: ssd_scan(*a, chunk=CHUNK, head_block=2,
                                  dtype=jnp.float32)[0]
    want = recurrence(*args.values(), seg)
    got = chunked(*args.values(), seg)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    weights = jnp.asarray(np.random.default_rng(1).standard_normal(
        want.shape), jnp.float32)
    g_want = jax.grad(lambda a: scalar(recurrence, weights, a, seg))(args)
    g_got = jax.grad(lambda a: scalar(chunked, weights, a, seg))(args)
    for name in args:
        scale = float(jnp.abs(g_want[name]).max())
        np.testing.assert_allclose(g_got[name], g_want[name], rtol=1e-5,
                                   atol=1e-5 * max(scale, 1.0),
                                   err_msg=name)


@pytest.mark.parametrize("case", ["boundary_inside_a_chunk",
                                  "several_sequences"])
def test_the_mixed_policy_stays_near_the_recurrence(case):
    """bfloat16 operands, float32 accumulation: four products each
    rounding both operands to 8 bits, so 2e-2 of the output's scale in
    value and 4e-2 of each gradient's (float32 reads 1e-5)."""
    seg = segments(CASES[case])
    args = inputs(len(CASES[case]), S, seed=3)
    mixed = lambda *a: ssd_scan(*a, chunk=CHUNK, dtype=jnp.bfloat16)[0]
    want = recurrence(*args.values(), seg)
    got = mixed(*args.values(), seg)
    assert got.dtype == jnp.bfloat16
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) \
        < 2e-2 * float(jnp.abs(want).max())
    weights = jnp.ones(want.shape, jnp.float32)
    g_want = jax.grad(lambda a: scalar(recurrence, weights, a, seg))(args)
    g_got = jax.grad(lambda a: scalar(mixed, weights, a, seg))(args)
    for name in args:
        assert float(jnp.abs(g_got[name] - g_want[name]).max()) \
            < 4e-2 * float(jnp.abs(g_want[name]).max()), name


def test_a_boundary_moves_the_output_and_is_counted():
    args = inputs(1, S, seed=5)
    whole = ssd_scan(*args.values(), segments([[S]]), chunk=CHUNK,
                     dtype=jnp.float32)
    cut = ssd_scan(*args.values(), segments([[11, S - 11]]), chunk=CHUNK,
                   dtype=jnp.float32)
    assert int(whole[1]) == 0 and int(cut[1]) == 1
    np.testing.assert_array_equal(whole[0][:, :11], cut[0][:, :11])
    assert float(jnp.abs(whole[0][:, 11:] - cut[0][:, 11:]).max()) > 1e-2
    seg = segments(CASES["several_sequences"])
    assert int(reset_masks(seg, CHUNK).resets) == 0 + 2 + 3


def test_a_sequence_that_does_not_fill_chunks_is_refused():
    args = inputs(1, 12)
    with pytest.raises(ValueError, match="multiple of the chunk"):
        ssd_scan(*args.values(), segments([[12]]), chunk=8)
