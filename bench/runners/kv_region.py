"""A YCSB usertable held as one protected heap of pages (the paper's DAX
region): a closed loop of read/update batches through one jitted step that
marks the pages it writes (``ProtectedStore.on_write``), each followed by
``ProtectedStore.tick``.

A record is YCSB's ``fieldcount`` fields of ``fieldlength`` bytes; as many
whole records as fit are packed into each page of ``page_bytes``, and a
page is one redundancy block.  A read returns a whole record, an update
writes one field.  With ``chips`` 4 the heap is split by pages over a 2x2
mesh and the step runs per shard under ``shard_map``; reads are summed
across the shards, each of which holds or zeroes a key.

Correctness, after the window:

* ``reads_wrong``: sampled batches' read values against the record-store
  reference (per field, the batch that last wrote it);
* ``rows_wrong``: the heap, page by page, against the reference;
* ``stale_old``: after ``settle``, checksums and stripes whose pages were
  last written ``max_vulnerable_steps`` or more ticks before the end and
  still disagree with the plain reference: the freshness guarantee;
* ``stale_flushed``: after ``flush``, every checksum, stripe and meta
  checksum against the plain reference;
* ``flip_missed``: a bit flipped in a page at rest that the patroller
  has not found and repaired from parity within two sweeps.

All are exact: the limit is 0.  The control breaks the freshness and
detection guarantees (no pass, no deadline, no patrol).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import numpy as np

from bench.generator import YcsbBatches
from bench.reference import records as ref_records
from bench.reference import redundancy as ref_red

AXES = ("data", "model")
GROUP_COUNTERS = ("updated", "coalesced", "overflowed", "deadline_fired",
                  "patrolled")


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


class KvRegion:
    # Every batch with ``hash(seed, batch) % READ_SAMPLE == 0`` has its read
    # values kept and compared.
    READ_SAMPLE = 16

    def __init__(self, cell, seed: int, spans, records: Optional[int] = None,
                 control: bool = False):
        self.cell = cell
        self.cfg = cell.config
        self.traffic = cell.traffic
        self.seed = int(seed)
        self.spans = spans
        self.chips = cell.chips
        if self.cfg["writeallfields"] or not self.cfg["readallfields"]:
            raise ValueError("reads return whole records, updates one field")
        self.records = int(records or self.cfg["recordcount"])
        self.fields = int(self.cfg["fieldcount"])
        self.field_words = int(self.cfg["fieldlength"]) // 4
        self.lanes = int(self.cfg["page_bytes"]) // 4
        self.per_page = self.lanes // (self.fields * self.field_words)
        if self.records % self.per_page:
            raise ValueError("records must fill whole pages")
        self.pages = self.records // self.per_page
        self.stripe = int(self.cfg["stripe_data_blocks"])
        self.control = control
        self.counters: Dict[str, Any] = {}

    # ---------------------------------------------------------------- set-up
    def policy(self):
        from repro.core import RedundancyPolicy
        prot = self.cfg["protection"]
        period, deadline = prot["period_steps"], prot["max_vulnerable_steps"]
        patrol = int(prot["patrol_bytes_per_tick"])
        if self.control:
            # The control breaks two guarantees: no pass is due within any
            # window and no deadline forces one (freshness), and no patrol
            # looks for corruption at rest (detection).
            period, deadline, patrol = 1 << 30, 0, 0
        return RedundancyPolicy.single(
            "vilamb", period_steps=period, max_vulnerable_steps=deadline,
            lanes_per_block=self.lanes, stripe_data_blocks=self.stripe,
            patrol_bytes_per_tick=patrol,
            period_cap=max(4096, period))

    def setup(self, warm_batches: Optional[int] = None) -> None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import ProtectedStore

        devs = jax.devices()[:self.chips]
        self.mesh = None
        if self.chips > 1:
            self.mesh = jax.make_mesh(
                (2, self.chips // 2), AXES, devices=devs,
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
            self.heap_sharding = NamedSharding(self.mesh, P(AXES, None))
            self.rep = NamedSharding(self.mesh, P())
        else:
            self.heap_sharding = jax.sharding.SingleDeviceSharding(devs[0])
            self.rep = self.heap_sharding
        self.shards = self.chips
        if self.pages % (self.shards * self.stripe):
            raise ValueError("pages must split into whole stripes per shard")
        self.local_rows = self.pages // self.shards

        seed, lanes, fw, n = self.seed, self.lanes, self.field_words, self.pages
        initial = (1, self.per_page, self.fields)
        make = jax.jit(lambda: ref_records.page_words(
            jnp, seed, jnp.full(initial, ref_records.INITIAL, jnp.uint32),
            jnp.arange(n, dtype=jnp.uint32), lanes, fw),
            out_shardings=self.heap_sharding)
        heap = make()
        spec = {"heap": P(AXES, None)} if self.mesh is not None else None
        self.store = ProtectedStore(self.policy(), mesh=self.mesh).attach(
            {"heap": heap}, specs=spec)
        self.red = self.store.init({"heap": heap})
        self.heap = heap
        self.label = next(iter(self.store.groups))

        self.gen = YcsbBatches(self.traffic, self.records, self.fields, seed,
                               ahead=int(self.traffic["batches_ahead"]))
        self.step_fn = self.make_step()
        # The reference record store: the batch that last wrote each field.
        self.last = np.full((self.records, self.fields), -1, np.int32)
        self.dirty_stripes = np.zeros((n // self.stripe,), bool)
        self.sampled: List[tuple] = []
        self.batch_i = 0
        self.lat: List[float] = []
        prot = self.cfg["protection"]
        warm = warm_batches if warm_batches is not None else (
            2 * int(prot["period_steps"]) + 2)
        for _ in range(warm):     # a due tick, a probe, every program
            self.one_batch(record=False)
        jax.block_until_ready((self.heap, self.red))

    def shard_body(self, heap, rk, wk, wf, b):
        """One shard's part of a batch: ``heap`` holds the pages
        ``[off, off + local)``; returns ``(heap, reads, dirty page mask)``
        with the reads summed over shards."""
        import jax
        import jax.numpy as jnp
        local, per, fw = self.local_rows, self.per_page, self.field_words
        rec = self.fields * fw
        off = 0
        if self.mesh is not None:
            off = jax.lax.axis_index(AXES) * local
        lr = rk // per - off
        hit = (lr >= 0) & (lr < local)
        cols = (rk % per)[:, None] * rec + jnp.arange(rec)[None, :]
        reads = jnp.where(hit[:, None],
                          heap[jnp.clip(lr, 0, local - 1)[:, None], cols], 0)
        mask = None
        if wk.shape[0]:
            vals = ref_records.field_values(jnp, self.seed, b, wk, wf, fw)
            lw = wk // per - off
            tgt = jnp.where((lw >= 0) & (lw < local), lw, local)
            cols = ((wk % per) * rec + wf * fw)[:, None] + jnp.arange(fw)
            # Whole pages are read, patched and written back: a scatter of
            # words into the heap makes XLA copy the whole heap.  Updates
            # that share a page patch its first copy, which alone is stored.
            n = wk.shape[0]
            first = jnp.argmax(tgt[:, None] == tgt[None, :], axis=1)
            rows = heap[jnp.minimum(tgt, local - 1)]
            rows = rows.at[first[:, None], cols].set(vals)
            keep = jnp.where(first == jnp.arange(n), tgt, local)
            heap = heap.at[keep].set(rows, mode="drop")
            mask = jnp.zeros((local,), bool).at[tgt].set(True, mode="drop")
        if self.mesh is not None:
            reads = jax.lax.psum(reads, AXES)
        return heap, reads, mask

    def make_step(self):
        """The jitted batch: reads see the heap before the batch's updates;
        updates write their records and mark them dirty."""
        import jax
        from jax.sharding import PartitionSpec as P

        store, writes = self.store, self.gen.updates

        def kv_batch(heap, red, rk, wk, wf, b):
            if self.mesh is None:
                heap, reads, mask = self.shard_body(heap, rk, wk, wf, b)
            else:
                heap, reads, mask = jax.shard_map(
                    self.shard_body, mesh=self.mesh,
                    in_specs=(P(AXES, None), P(), P(), P(), P()),
                    out_specs=(P(AXES, None), P(), P(AXES) if writes else None),
                    check_vma=False)(heap, rk, wk, wf, b)
            if writes:
                red = store.on_write(red, events={"heap": mask})
            return heap, red, reads

        shard = None
        if self.mesh is not None:
            shard = (self.heap_sharding, store.red_shardings(), self.rep)
        return jax.jit(kv_batch, donate_argnums=(0, 1), out_shardings=shard)

    # ---------------------------------------------------------------- window
    def sampled_batch(self, b: int) -> bool:
        return hash((self.seed, b)) % self.READ_SAMPLE == 0

    def one_batch(self, record: bool = True) -> None:
        b = self.batch_i
        rk, wk, wf = self.gen.batch(b)
        keep = record and self.sampled_batch(b)
        if keep:
            expect = self.last[rk]
        t0 = time.perf_counter()
        with self.spans("write"):
            self.heap, self.red, reads = self.step_fn(
                self.heap, self.red, rk, wk, wf, np.uint32(b))
        with self.spans("read"):
            got = np.asarray(reads)
        if record:
            self.lat.append(time.perf_counter() - t0)
        if keep:
            self.sampled.append((rk, expect, got))
        self.last[wk, wf] = b
        self.dirty_stripes[wk // (self.per_page * self.stripe)] = True
        with self.spans("tick"):
            self.red, rep = self.store.tick({"heap": self.heap}, self.red, b)
        if rep.repaired:
            self.heap = rep.repaired["heap"]
        if self.label in rep.updated and self.label not in rep.coalesced:
            if record:
                self.counters["pass_stripes"] = (
                    self.counters.get("pass_stripes", 0)
                    + int(self.dirty_stripes.sum()))
            self.dirty_stripes[:] = False
        if record:
            for k in GROUP_COUNTERS:
                if self.label in getattr(rep, k) or "heap" in getattr(rep, k):
                    self.counters[k] = self.counters.get(k, 0) + 1
        self.batch_i += 1

    def window(self, seconds: float) -> Dict[str, float]:
        self.lat = []
        self.counters = {}
        first = self.batch_i
        t0 = time.perf_counter()
        end = t0 + seconds
        while time.perf_counter() < end:
            self.one_batch()
        elapsed = time.perf_counter() - t0
        batches = self.batch_i - first
        ticks = self.spans.spans.get("tick", [])
        if ticks:
            a, b = max(ticks, key=lambda t: t[1] - t[0])
            self.counters.update(tick_max_ms=(b - a) * 1e3,
                                 tick_max_at_s=a - t0,
                                 tick_total_s=sum(b - a for a, b in ticks))
        ops = batches * (self.gen.reads + self.gen.updates)
        self.counters.update(steps=batches, ops=ops, window_s=elapsed)
        p99 = float(np.quantile(np.asarray(self.lat), 0.99,
                                method="inverted_cdf"))
        self.counters.update(batch_max_ms=max(self.lat) * 1e3,
                             batch_total_s=sum(self.lat))
        return {"kv_ops_per_s": ops / elapsed, "kv_p99_ms": p99 * 1e3}

    def attempted(self) -> int:
        return int(self.counters.get("ops", 0))

    def failed(self) -> int:
        return int(self.counters.get("reads_failed", 0))

    def close(self) -> None:
        self.heap = self.red = self.store = self.step_fn = None
        self.sampled = []

    def work(self) -> Dict[str, float]:
        """Algorithm 1's bytes over the window's passes: each stripe dirtied
        since the previous pass reads its P data blocks once and writes its
        parity block and P checksums once."""
        per = self.stripe * self.lanes * 4 + self.lanes * 4 + self.stripe * 4
        return {"update_bytes": self.counters.get("pass_stripes", 0) * per}

    # ----------------------------------------------------------- correctness
    def check(self) -> List[Check]:
        import jax
        end = self.batch_i - 1
        deadline = int(self.cfg["protection"]["max_vulnerable_steps"])
        reads_wrong = self.check_reads()
        self.red = self.store.settle(self.red, {"heap": self.heap}, step=end)
        self.heap = self.store.take_repaired().get("heap", self.heap)
        # Pages last written at step > end - deadline may legitimately be
        # vulnerable; every older one must be covered.
        young = (self.last > end - deadline).reshape(
            self.pages, -1).any(axis=1)
        rows_wrong, stale_old, _ = self.compare(young)
        self.red = self.store.flush({"heap": self.heap}, self.red, end + 1)
        _, stale_flushed, meta_wrong = self.compare(np.zeros_like(young))
        flip_missed = self.check_detection(end + 2)
        jax.block_until_ready(self.red)
        return [Check("reads_wrong", reads_wrong, 0),
                Check("rows_wrong", rows_wrong, 0),
                Check("stale_old", stale_old, 0),
                Check("stale_flushed", stale_flushed + meta_wrong, 0),
                Check("flip_missed", flip_missed, 0)]

    def check_detection(self, step: int) -> int:
        """Flip one bit of a page at rest and tick without writes: the
        patroller must find it within two sweeps and repair it from parity.
        Returns 1 when the page is not back to its reference value."""
        import jax
        import jax.numpy as jnp
        from repro.scrub.patrol import PROBE_FORCE_TICKS

        rng = np.random.default_rng((self.seed, 1))
        row = int(rng.integers(self.pages))
        lane, bit = int(rng.integers(self.lanes)), int(rng.integers(32))
        flip = jax.jit(lambda h: h.at[row, lane].set(
            h[row, lane] ^ jnp.uint32(1 << bit)), donate_argnums=(0,),
            out_shardings=self.heap_sharding)
        self.heap = flip(self.heap)
        window = max(1, int(self.cfg["protection"]["patrol_bytes_per_tick"])
                     // (self.lanes * 4))
        sweep = -(-self.local_rows // window)
        for _ in range(2 * sweep * (PROBE_FORCE_TICKS + 1) + 16):
            self.red, rep = self.store.tick({"heap": self.heap}, self.red,
                                            step)
            step += 1
            if rep.repaired:
                self.heap = rep.repaired["heap"]
                break
        want = ref_records.page_words(
            np, self.seed, self.versions(row, 1), np.array([row]),
            self.lanes, self.field_words)
        got = np.asarray(jax.device_get(self.heap[row]))
        return int(not np.array_equal(want[0], got))

    def check_reads(self) -> int:
        wrong = 0
        for rk, expect, got in self.sampled:
            ver = np.where(expect < 0, ref_records.INITIAL, expect)
            want = ref_records.record_words(np, self.seed, ver.astype(np.uint32),
                                            rk, self.field_words)
            wrong += int(np.any(want != got, axis=1).sum())
        self.counters["reads_failed"] = wrong
        self.counters["reads_checked"] = sum(len(s[0]) for s in self.sampled)
        return wrong

    def versions(self, page0: int, n: int) -> np.ndarray:
        """``(n, R, F)`` field versions of the pages from ``page0``, as the
        reference takes them."""
        per = self.per_page
        last = self.last[page0 * per:(page0 + n) * per]
        return np.where(last < 0, ref_records.INITIAL, last).astype(
            np.uint32).reshape(n, per, self.fields)

    def _parts(self):
        """Per shard: (first global row, heap, checksums, parity, meta),
        each a single-device array."""
        leaf = self.red["heap"]
        if self.mesh is None:
            return [(0, self.heap, leaf.checksums, leaf.parity, leaf.meta_ck)]
        def by_row(arr):
            return sorted(((s.index[0].start or 0, s.data)
                           for s in arr.addressable_shards),
                          key=lambda t: t[0])
        heaps, cks = by_row(self.heap), by_row(leaf.checksums)
        pars, metas = by_row(leaf.parity), by_row(leaf.meta_ck)
        return [(h[0], h[1], c[1], p[1], m[1])
                for h, c, p, m in zip(heaps, cks, pars, metas)]

    def compare(self, exempt_rows: np.ndarray):
        """(pages wrong, stale checksums + stripes outside ``exempt_rows``,
        meta checksums wrong) against the reference heap."""
        import jax
        import jax.numpy as jnp
        blk = min(self.local_rows, 32768)
        seed, lanes, fw, stripe = (self.seed, self.lanes, self.field_words,
                                   self.stripe)

        @jax.jit
        def cmp(heap, ck, par, ver, start, row0):
            pages = row0 + start + jnp.arange(blk, dtype=jnp.uint32)
            want = ref_records.page_words(jnp, seed, ver, pages, lanes, fw)
            got = jax.lax.dynamic_slice_in_dim(heap, start, blk)
            ck_ref = ref_red.block_checksums(jnp, want, start)
            ck_got = jax.lax.dynamic_slice_in_dim(ck, start, blk)
            par_ref = ref_red.stripe_parity(jnp, want, stripe)
            par_got = jax.lax.dynamic_slice_in_dim(par, start // stripe,
                                                   blk // stripe)
            return (jnp.any(want != got, axis=1), ck_ref != ck_got,
                    jnp.any(par_ref != par_got, axis=1), ck_ref)

        rows_wrong = stale = meta_wrong = 0
        for row0, heap, ck, par, meta in self._parts():
            dev = next(iter(heap.devices()))
            cks = []
            for start in range(0, self.local_rows, blk):
                g0 = row0 + start
                ver = jax.device_put(self.versions(g0, blk), dev)
                rw, cw, pw, ckr = cmp(heap, ck, par, ver, np.int32(start),
                                      np.uint32(row0))
                rw, cw, pw = np.asarray(rw), np.asarray(cw), np.asarray(pw)
                ex = exempt_rows[g0:g0 + blk]
                ex_stripe = ex.reshape(-1, stripe).any(axis=1)
                rows_wrong += int(rw.sum())
                stale += int((cw & ~ex).sum()) + int((pw & ~ex_stripe).sum())
                cks.append(np.asarray(ckr))
            want_meta = ref_red.meta_checksum(np, np.concatenate(cks))
            meta_wrong += int(np.asarray(meta).reshape(-1)[0] != want_meta)
        return rows_wrong, stale, meta_wrong


def make(cell, seed: int, spans) -> KvRegion:
    return KvRegion(cell, seed, spans)
