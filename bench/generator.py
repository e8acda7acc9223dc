"""The one traffic generator: every mix under ``bench/traffic/`` is a JSON
file of parameters that names a ``generator`` kind defined here.

* ``ycsb`` — YCSB CoreWorkload operations over ``[0, recordcount)``:
  batches of ``ops_per_batch`` operations holding the mix's exact
  read/update proportion.  Keys follow YCSB's ScrambledZipfianGenerator
  (``requestdistribution`` ``zipfian``: a Zipfian over 1e10 items at
  ``zipfian_constant``, hashed with FNV-1a-64 onto the records).  An update
  writes one field, drawn uniformly from the record's ``fieldcount`` (YCSB's
  ``writeallfields=false``).  Update keys are distinct within a batch (a
  duplicate is drawn again), so a batch has one writer per record.
* ``lm_tokens`` — language-model batches ``(batch, seq_len)`` of token ids
  drawn Zipf(``token_zipf``) over the vocabulary, labels shifted by one.

The same seed gives the same stream; any seed gives the same sizes.
Seeds may be any non-negative integer (numpy seeds take arbitrary size).
"""
from __future__ import annotations

import numpy as np

ZIPF_ITEMS = 10_000_000_000          # ScrambledZipfianGenerator.ITEM_COUNT
ZIPF_ZETAN = 26.46902820178302       # zeta(ITEM_COUNT, 0.99), YCSB's constant
FNV_OFFSET = np.uint64(0xCBF29CE484222325)
FNV_PRIME = np.uint64(1099511628211)


def fnv64(v: np.ndarray) -> np.ndarray:
    """YCSB ``Utils.fnvhash64``: FNV over the 8 low-first octets, then
    ``Math.abs`` of the signed result."""
    v = v.astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h = (h ^ (v & np.uint64(0xFF))) * FNV_PRIME
            v = v >> np.uint64(8)
    return np.abs(h.view(np.int64))


class ScrambledZipfian:
    """YCSB's ScrambledZipfianGenerator over ``[0, n)``."""

    def __init__(self, n: int, theta: float = 0.99):
        if theta != 0.99:
            raise ValueError("YCSB precomputes zeta only for 0.99")
        self.n = n
        self.theta = theta
        zeta2 = 1.0 + 0.5 ** theta
        self.alpha = 1.0 / (1.0 - theta)
        self.eta = ((1.0 - (2.0 / ZIPF_ITEMS) ** (1.0 - theta))
                    / (1.0 - zeta2 / ZIPF_ZETAN))
        self.half_pow = 0.5 ** theta

    def draw(self, rng: np.random.Generator, size: int) -> np.ndarray:
        u = rng.random(size)
        uz = u * ZIPF_ZETAN
        ranks = (ZIPF_ITEMS * np.power(self.eta * u - self.eta + 1.0,
                                       self.alpha)).astype(np.int64)
        ranks = np.where(uz < 1.0 + self.half_pow, 1, ranks)
        ranks = np.where(uz < 1.0, 0, ranks)
        return fnv64(ranks) % self.n


def key_chooser(traffic: dict, records: int) -> ScrambledZipfian:
    dist = traffic["requestdistribution"]
    if dist != "zipfian":
        raise ValueError(f"unknown requestdistribution {dist!r}")
    return ScrambledZipfian(records, traffic.get("zipfian_constant", 0.99))


class YcsbBatches:
    """Batches of ``(read_keys, update_keys, update_fields)``, int32, from
    the seed.

    Batches come in chunks of ``CHUNK``, chunk ``c`` drawn from its own
    stream ``(seed, c)``, so batch ``i`` is the same however far ahead the
    generator was asked to work; ``ahead`` batches are made at once
    (set-up), so the closed loop only indexes into arrays.
    """
    CHUNK = 1024

    def __init__(self, traffic: dict, records: int, fields: int, seed: int,
                 ahead: int = 0):
        ops = int(traffic["ops_per_batch"])
        self.reads = round(ops * float(traffic["readproportion"]))
        self.updates = round(ops * float(traffic["updateproportion"]))
        if self.reads + self.updates != ops:
            raise ValueError("read and update proportions must cover the batch")
        if self.updates > records:
            raise ValueError("more distinct updates per batch than records")
        self.chooser = key_chooser(traffic, records)
        self.fields = fields
        self.seed = seed
        self._chunks: dict = {}
        for c in range(-(-ahead // self.CHUNK)):
            self._chunk(c)

    def _distinct_rows(self, rng, rows: int) -> np.ndarray:
        """``(rows, updates)`` keys, distinct within each row: every key
        equal to an earlier one in its row is drawn again, in place."""
        w = self.chooser.draw(rng, rows * self.updates).reshape(
            rows, self.updates)
        while True:
            order = np.argsort(w, axis=1, kind="stable")
            s = np.take_along_axis(w, order, axis=1)
            dup = np.zeros(w.shape, bool)
            np.put_along_axis(dup, order[:, 1:], s[:, 1:] == s[:, :-1],
                              axis=1)
            n = int(dup.sum())
            if not n:
                return w
            w[dup] = self.chooser.draw(rng, n)

    def _chunk(self, c: int):
        got = self._chunks.get(c)
        if got is None:
            rng = np.random.default_rng((self.seed, c))
            r = self.chooser.draw(rng, self.CHUNK * self.reads).reshape(
                self.CHUNK, self.reads).astype(np.int32)
            w = self._distinct_rows(rng, self.CHUNK).astype(np.int32)
            f = rng.integers(0, self.fields, size=w.shape, dtype=np.int32)
            got = self._chunks[c] = (r, w, f)
        return got

    def batch(self, i: int):
        return tuple(a[i % self.CHUNK] for a in self._chunk(i // self.CHUNK))


class LmBatches:
    """Token batches for training step ``i`` (a pure function of the seed
    and ``i``): ``tokens`` and next-token ``labels``, int32."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.batch = int(traffic["batch"])
        self.seq = int(traffic["seq_len"])
        self.a = float(traffic["token_zipf"])
        self.vocab = vocab
        self.seed = seed

    def numpy(self, i: int) -> dict:
        rng = np.random.default_rng((self.seed, i))
        z = rng.zipf(self.a, size=(self.batch, self.seq + 1))
        stream = ((z - 1) % self.vocab).astype(np.int32)
        return {"tokens": stream[:, :-1], "labels": stream[:, 1:].copy()}
