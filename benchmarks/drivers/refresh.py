"""``refresh``: TPC-H's refresh stream (Clause 5.3), one closed loop.

One unit is one refresh pair on the resident tables, through one
``ServeSession``: RF1 (Clause 2.5.2) ingests update set ``i``'s orders,
then its lineitems, handed over as host columns; RF2 (Clause 2.5.3)
deletes the oldest live orders and their lineitems by order key
(``harness/tpch_refresh.py``).  Each write is awaited before the next.
Update sets are made on the host from ``(seed, i)`` by a producer
thread, ahead of the loop and outside the timed writes.  The host does
not wait for a write's device work, but RF2 starts once the pair before
has finished on the device: the chip then holds this pair's RF1 while
the host sends RF2, and RF2 while the host makes the next RF1, and no
more than that is in flight (each write copies the columns it writes).
The warm-up is
pair 0 and catches nothing; no pair starts after the window's seconds,
and the window ends when the tables' row counts after the last pair
are read, so it holds all of that pair's device work.  Reports
``stream_s``, the window's time over the pairs completed.  The rows
each pair deleted come from the tables' device row counts, kept after
every pair and read once after the window: a read per pair would make
the host wait for every delete.

The check: the refreshed orders and lineitem against the pandas
reference state (row-hash multisets, ``wrong_rows``), and q18 on the
refreshed tables, with the live order-key bounds, against pandas.
"""
import queue
import threading
import time
import traceback

from benchmarks.harness import compare, tpch_data, tpch_refresh
from benchmarks.harness import tpch_reference as ref
from benchmarks.harness.traffic import Window, span, warn

TABLES = (("orders", "o_orderkey"), ("lineitem", "l_orderkey"))
AHEAD = 4  # update sets the producer keeps ready


class Driver:
    def __init__(self, spec, dep, ctx, seed: int):
        from cylon_tpu.serve import ServeSession
        self.dep, self.ctx, self.seed = dep, ctx, seed
        self.scale = dep.scale
        self.session = ServeSession(ctx, tables=dep.tables,
                                    **spec.get("session", {}))
        self.pairs = 0          # pairs applied, the warm-up's included
        self.units = 0          # pairs completed in the window
        self.missing = 0
        self.rows = {"in": 0, "deleted": 0, "scanned": 0}
        self._trail = []        # per pair: (rows in, device row counts)
        self._last = None       # the device row counts after the last pair
        self.widths = {t: sum(c.data.dtype.itemsize
                              for c in dep.tables[t].columns)
                       for t, _ in TABLES}
        self.key_bytes = {t: dep.tables[t].column(k).data.dtype.itemsize
                          for t, k in TABLES}
        self._sets = queue.Queue(maxsize=AHEAD)
        self._stop = threading.Event()
        self._producer = threading.Thread(target=self._produce,
                                          name="refresh-sets", daemon=True)
        self._producer.start()
        self._kept = None

    def _produce(self) -> None:
        i = 0
        while not self._stop.is_set():
            sets = tpch_refresh.update_sets(self.scale, self.seed, i, 1)
            while not self._stop.is_set():
                try:
                    self._sets.put(sets, timeout=0.1)
                    break
                except queue.Full:
                    continue
            i += 1

    def _pair(self, keep: bool) -> None:
        import jax
        sets = self._sets.get()
        i = self.pairs
        keys = tpch_refresh.rf2_keys(self.scale, i)
        with span("rf1"):
            for t, _ in TABLES:
                self.session.ingest(t, sets[t]).result()
        if self._last is not None:
            jax.block_until_ready(self._last)
        with span("rf2"):
            for t, k in TABLES:
                self.session.delete(t, k, keys).result()
        self.pairs += 1
        self._last = [self.dep.tables[t].counts for t, _ in TABLES]
        if keep:
            self._trail.append({
                t: (len(sets[t][tpch_data.COLUMN_ORDER[t][0]]),
                    self.dep.tables[t].counts) for t, _ in TABLES})

    def _rows_now(self):
        """The tables' row counts, read: waits for their last write."""
        return {t: self.dep.tables[t].num_rows for t, _ in TABLES}

    def _tally(self, start) -> None:
        """Rows written, deleted and key bytes scanned, per table, from
        the row counts kept after each pair."""
        import jax
        trail = jax.device_get([[c for _, c in p.values()]
                                for p in self._trail])
        rows = dict(start)
        for pair, after in zip(self._trail, trail):
            for (t, (n_in, _)), n_after in zip(pair.items(), after):
                gone = rows[t] + n_in - int(n_after.sum())
                self.rows["in"] += n_in * self.widths[t]
                self.rows["deleted"] += gone * self.widths[t]
                self.rows["scanned"] += (rows[t] + n_in) \
                    * self.key_bytes[t]
                rows[t] = int(n_after.sum())
        self._trail = []

    def warm(self) -> None:
        self._pair(keep=False)
        self._rows_now()

    def run(self, seconds: float) -> Window:
        w = Window()
        start = self._rows_now()
        t0 = time.perf_counter()
        with span("window"):
            while time.perf_counter() - t0 < seconds:
                w.attempted += 1
                try:
                    self._pair(keep=True)
                except Exception:  # the tables' state is now unknown
                    warn(f"refresh pair failed:\n{traceback.format_exc()}")
                    self.missing += 1
                    break
                w.units += 1
            if not self.missing:
                self._rows_now()
        w.seconds = time.perf_counter() - t0
        self._tally(start)
        w.failed = self.missing
        w.e2e["stream_s"] = w.seconds / max(w.units, 1)
        self.units = w.units
        return w

    def release(self) -> None:
        """Read back the refreshed tables and q18's answer, then close
        the session."""
        from cylon_tpu.parallel import run_pipeline
        self._stop.set()
        dicts = tpch_data.dictionaries(self.scale)
        lo_hi = tpch_refresh.live_key_range(self.scale, self.pairs)
        tables = self.dep.tables
        try:
            q18 = run_pipeline(lambda: self.ctx.optimize(
                tpch_refresh.q18_op(self.ctx, lo_hi), tables)).to_pandas()
        except Exception:  # a missing answer (the check counts it)
            warn(f"q18 failed:\n{traceback.format_exc()}")
            self.missing += 1
            q18 = None
        self._kept = {"q18": q18, **{
            t: tpch_refresh.table_arrays(tables[t], dicts)
            for t, _ in TABLES}}
        self.session.close()
        self.session = None  # it holds the tables: let them go

    def facts(self):
        """Least HBM bytes per pair, for the rooflines: RF1's rows
        written once; RF2's key columns read once and its rows."""
        n = max(self.units, 1)
        return {"insert_bytes": self.rows["in"] / n,
                "delete_bytes": (self.rows["scanned"]
                                 + self.rows["deleted"]) / n}

    def check(self, ref_dir: str):
        base = tpch_refresh.base_frames(self.scale, self.seed,
                                        self.ctx.sharding())
        want = tpch_refresh.reference_state(base, self.scale, self.seed,
                                            self.pairs)
        want["customer"] = base.pop("customer")
        del base
        numbers = {"wrong_rows": 0, "rel_err": 0.0, "missing": self.missing}
        for t, _ in TABLES:
            numbers["wrong_rows"] += tpch_refresh.rows_mismatch(
                self._kept[t], want[t])
        got = self._kept["q18"]
        if got is None:
            return numbers
        pairs = ref.COLUMNS["q18"]
        got = got[[a for a, _ in pairs]].rename(columns=dict(pairs))
        return compare.merge(numbers, compare.compare_answer(
            got, ref.answer("q18", want), ref.KEYS["q18"]))
