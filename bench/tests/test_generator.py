"""The YCSB and token generators: deterministic per seed, the mix's exact
proportions, distinct update keys, YCSB's skew."""
import numpy as np

from bench.generator import LmBatches, ScrambledZipfian, YcsbBatches, fnv64

A = {"ops_per_batch": 256, "readproportion": 0.5, "updateproportion": 0.5,
     "requestdistribution": "zipfian"}
C = dict(A, readproportion=1.0, updateproportion=0.0)


def test_same_seed_same_batches_any_seed_same_sizes():
    a = YcsbBatches(A, 4096, 10, 2 ** 40 + 3, ahead=2048)
    b = YcsbBatches(A, 4096, 10, 2 ** 40 + 3)
    c = YcsbBatches(A, 4096, 10, 11)
    for i in list(range(50)) + [1500, 1023, 1024]:
        for x, y, z in zip(a.batch(i), b.batch(i), c.batch(i)):
            np.testing.assert_array_equal(x, y)
            assert x.shape == z.shape == (128,)
    assert not np.array_equal(a.batch(100)[0], c.batch(100)[0])


def test_update_keys_distinct_within_a_batch():
    g = YcsbBatches(A, 1024, 10, 5)
    fields = []
    for i in range(512):
        r, w, f = g.batch(i)
        assert len(np.unique(w)) == len(w) == 128
        assert r.min() >= 0 and r.max() < 1024 and w.max() < 1024
        fields.append(f)
    # One field per update, drawn uniformly over the record's ten.
    counts = np.bincount(np.concatenate(fields), minlength=10)
    assert len(counts) == 10 and counts.min() > 0.9 * counts.mean()


def test_read_only_mix_has_no_updates():
    r, w, f = YcsbBatches(C, 1024, 10, 5).batch(0)
    assert r.shape == (256,) and w.shape == f.shape == (0,)


def test_scrambled_zipfian_is_skewed_and_spread():
    n = 1 << 16
    keys = ScrambledZipfian(n).draw(np.random.default_rng(1), 200_000)
    counts = np.sort(np.bincount(keys, minlength=n))[::-1]
    top = counts[: n // 100].sum() / counts.sum()
    assert 0.2 < top < 0.9          # hot keys, as Zipf 0.99 gives
    assert (counts > 0).sum() > n // 4   # scrambled over the whole range
    # The hottest item (rank 0) lands where FNV puts it, not at key 0.
    assert np.argmax(np.bincount(keys, minlength=n)) == fnv64(
        np.array([0]))[0] % n


def test_token_batches_deterministic_with_shifted_labels():
    t = {"batch": 2, "seq_len": 16, "token_zipf": 1.1}
    a, b = LmBatches(t, 512, 9).numpy(3), LmBatches(t, 512, 9).numpy(3)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    assert a["tokens"].max() < 512
    assert not np.array_equal(a["tokens"], LmBatches(t, 512, 9).numpy(4)[
        "tokens"])
