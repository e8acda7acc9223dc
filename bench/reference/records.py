"""Plain record store: the value every field holds after any sequence of
writes, as a function of the seed, the key, the field and the batch that
last wrote that field.

A record is YCSB's ``fieldcount`` fields of ``fieldlength`` bytes, kept as
uint32 words.  The words of field ``f`` of record ``key`` are a hash of
``(seed, version, key, word)``, where ``version`` is the index of the batch
that last wrote the field, or ``INITIAL`` for the value the heap starts
with, and ``word`` is the word's index within the record.  So the reference
record store is the table of last-writer versions per field that the load
generator keeps, and any record, read or at rest, is recomputed from it.

Records are packed into pages: page ``p`` holds records ``p * R .. p * R +
R - 1`` one after another from its first word; the words after the last
record are zero.
"""
from __future__ import annotations

from .redundancy import fmix32, u32

INITIAL = 0xFFFFFFFF
K1 = 0x9E3779B1
K2 = 0x85EBCA77
K3 = 0xC2B2AE3D


def seed_words(seed: int) -> tuple:
    """A seed of up to 64 bits as two uint32 words."""
    return seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF


def field_hash(xp, seed: int, versions, keys):
    """The hash of ``(seed, version, key)`` that a field's words derive
    from; ``versions`` and ``keys`` broadcast against each other."""
    lo, hi = seed_words(seed)
    h = fmix32(xp, versions.astype(xp.uint32) ^ u32(xp, hi) * u32(xp, K2))
    return fmix32(xp, (h ^ u32(xp, lo)) ^ keys.astype(xp.uint32) * u32(xp, K1))


def words(xp, h, inner):
    """The word at index ``inner`` of its record, from its field's hash."""
    return fmix32(xp, h ^ inner.astype(xp.uint32) * u32(xp, K3))


def record_words(xp, seed: int, versions, keys, field_words: int):
    """``(n, F * field_words)`` words of the records ``keys`` (shape
    ``(n,)``) whose field ``f`` was last written at ``versions[:, f]``
    (shape ``(n, F)``, ``INITIAL`` for unwritten)."""
    n_fields = versions.shape[1]
    inner = xp.arange(n_fields * field_words, dtype=xp.uint32)
    ver = versions[:, inner // u32(xp, field_words)]
    return words(xp, field_hash(xp, seed, ver, keys[:, None]), inner[None, :])


def field_values(xp, seed: int, version, keys, fields, field_words: int):
    """``(n, field_words)`` words that the write of ``fields[i]`` of record
    ``keys[i]`` by batch ``version`` stores."""
    inner = (fields.astype(xp.uint32)[:, None] * u32(xp, field_words)
             + xp.arange(field_words, dtype=xp.uint32)[None, :])
    h = field_hash(xp, seed, xp.asarray(version, dtype=xp.uint32),
                   keys[:, None])
    return words(xp, h, inner)


def page_words(xp, seed: int, versions, pages, lanes: int, field_words: int):
    """``(P, lanes)`` words of the pages ``pages`` (shape ``(P,)``), whose
    records' field versions are ``versions`` of shape ``(P, R, F)`` (or
    ``(1, R, F)``, the same for every page)."""
    _, per_page, n_fields = versions.shape
    rec = n_fields * field_words
    w = xp.arange(lanes, dtype=xp.uint32)
    slot = xp.minimum(w // u32(xp, rec), u32(xp, per_page - 1))
    inner = w % u32(xp, rec)
    ver = versions[:, slot, inner // u32(xp, field_words)]
    keys = pages.astype(xp.uint32)[:, None] * u32(xp, per_page) + slot[None, :]
    out = words(xp, field_hash(xp, seed, ver, keys), inner[None, :])
    return xp.where(w[None, :] < u32(xp, per_page * rec), out, u32(xp, 0))
