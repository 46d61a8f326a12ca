import numpy as np

from traffic.bigram import Traffic

JOB = {"rows_per_chip": 3, "chips": 2, "seq_len": 300, "noise": 0.1}


def naive(t: Traffic, step: int) -> np.ndarray:
    """The same bigram chain, one position at a time."""
    rng = np.random.default_rng([t.seed, 1, step])
    fresh = rng.random((t.rows, t.seq)) < t.noise
    fresh[:, 0] = True
    draw = rng.integers(0, t.vocab, size=(t.rows, t.seq), dtype=np.int64)
    out = np.zeros((t.rows, t.seq), np.int64)
    for r in range(t.rows):
        cur = 0
        for i in range(t.seq):
            cur = draw[r, i] if fresh[r, i] else (t.a * cur + t.b) % t.vocab
            out[r, i] = cur
    return out


def test_matches_the_chain_computed_step_by_step():
    t = Traffic(JOB, 50304, 2 ** 40 + 7)
    for step in (0, 5):
        np.testing.assert_array_equal(t.batch(step)["tokens"], naive(t, step))


def test_same_seed_same_batches_new_rows_every_step():
    a = Traffic(JOB, 25600, 3_000_000_001)
    b = Traffic(JOB, 25600, 3_000_000_001)
    np.testing.assert_array_equal(a.batch(4)["tokens"], b.batch(4)["tokens"])
    x, y = a.batch(0)["tokens"], a.batch(1)["tokens"]
    assert x.shape == (6, 300) and x.dtype == np.int32
    assert not np.array_equal(x, y)
    assert len({r.tobytes() for r in np.concatenate([x, y])}) == 12
    assert x.min() >= 0 and x.max() < 25600
