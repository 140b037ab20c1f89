"""Data library tests (reference pattern: python/ray/data/tests — local
ray.init + operator unit tests)."""

import os
import tempfile

import numpy as np
import pytest

import ray_tpu
from ray_tpu.data import (BlockAccessor, Dataset, from_items, from_numpy,
                          range as ds_range)


class TestDatasetBasics:
    def test_range_count(self, ray_start):
        assert ds_range(100, parallelism=4).count() == 100

    def test_from_items_take(self, ray_start):
        ds = from_items([{"a": i} for i in range(10)], parallelism=3)
        assert [r["a"] for r in ds.take(5)] == [0, 1, 2, 3, 4]

    def test_map_batches(self, ray_start):
        def double(batch):
            return {"id": batch["id"] * 2}
        out = ds_range(10, parallelism=2).map_batches(double).take_all()
        assert sorted(r["id"] for r in out) == [2 * i for i in range(10)]

    def test_map_and_filter(self, ray_start):
        ds = (ds_range(20, parallelism=2)
              .map(lambda r: {"id": r["id"], "sq": int(r["id"]) ** 2})
              .filter(lambda r: r["sq"] % 2 == 0))
        rows = ds.take_all()
        assert all(r["sq"] == r["id"] ** 2 for r in rows)
        assert all(r["sq"] % 2 == 0 for r in rows)

    def test_fused_stage_chain(self, ray_start):
        ds = (ds_range(12, parallelism=3)
              .map_batches(lambda b: {"id": b["id"] + 1})
              .map_batches(lambda b: {"id": b["id"] * 10}))
        assert sorted(r["id"] for r in ds.take_all()) == \
            [10 * (i + 1) for i in range(12)]

    def test_flat_map(self, ray_start):
        ds = from_items([1, 2], parallelism=1).flat_map(
            lambda r: [{"v": r["item"]}, {"v": r["item"] * 100}])
        assert sorted(r["v"] for r in ds.take_all()) == [1, 2, 100, 200]

    def test_repartition_and_shuffle(self, ray_start):
        ds = ds_range(100, parallelism=2).repartition(5).materialize()
        assert ds.num_blocks() == 5
        shuffled = ds_range(100, parallelism=2).random_shuffle(seed=0)
        ids = [r["id"] for r in shuffled.take_all()]
        assert sorted(ids) == list(range(100))
        assert ids != list(range(100))

    def test_schema(self, ray_start):
        s = from_numpy({"x": np.zeros((5, 3), np.float32)}).schema()
        assert s["x"] == "float32"

    def test_split(self, ray_start):
        shards = ds_range(90, parallelism=4).split(3)
        counts = [s.count() for s in shards]
        assert counts == [30, 30, 30]
        all_ids = sorted(r["id"] for s in shards for r in s.take_all())
        assert all_ids == list(range(90))


class TestIterBatches:
    def test_exact_batches(self, ray_start):
        batches = list(ds_range(64, parallelism=4).iter_batches(
            batch_size=16))
        assert len(batches) == 4
        assert all(len(b["id"]) == 16 for b in batches)

    def test_remainder(self, ray_start):
        batches = list(ds_range(70, parallelism=4).iter_batches(
            batch_size=16))
        assert sum(len(b["id"]) for b in batches) == 70
        batches = list(ds_range(70, parallelism=4).iter_batches(
            batch_size=16, drop_last=True))
        assert all(len(b["id"]) == 16 for b in batches)

    def test_device_put_iterator(self, ray_start):
        import jax
        from ray_tpu.data import device_put_iterator
        it = ds_range(32, parallelism=2).iter_batches(batch_size=16)
        dev_batches = list(device_put_iterator(it))
        assert len(dev_batches) == 2
        assert all(isinstance(b["id"], jax.Array) for b in dev_batches)


class TestIO:
    def test_parquet_roundtrip(self, ray_start):
        import pyarrow as pa
        import pyarrow.parquet as pq
        with tempfile.TemporaryDirectory() as tmp:
            for i in range(3):
                pq.write_table(
                    pa.table({"x": list(np.arange(i * 10, (i + 1) * 10))}),
                    os.path.join(tmp, f"part{i}.parquet"))
            ds = Dataset.read_parquet(os.path.join(tmp, "*.parquet"))
            assert ds.count() == 30
            out = ds.map_batches(lambda b: {"x": b["x"] * 2}).take_all()
            assert sorted(r["x"] for r in out) == [2 * i for i in range(30)]

    def test_csv(self, ray_start):
        with tempfile.TemporaryDirectory() as tmp:
            p = os.path.join(tmp, "t.csv")
            with open(p, "w") as f:
                f.write("a,b\n1,x\n2,y\n")
            rows = Dataset.read_csv(p).take_all()
            assert [int(r["a"]) for r in rows] == [1, 2]


from ray_tpu import data


class TestDistributedShuffle:
    """Two-stage task shuffle (reference: _internal/planner/exchange/) +
    streaming execution."""

    def test_shuffle_runs_as_tasks_not_driver(self, ray_start):
        ds = data.range(4000, parallelism=8).random_shuffle(seed=7)
        from ray_tpu.data.executor import execute
        out = execute(ds)
        # Outputs are refs produced by reduce tasks: the driver never held
        # the concatenated data.
        assert all(isinstance(b, ray_tpu.ObjectRef) for b in out)
        rows = sorted(r["id"] for r in ds.take_all())
        assert rows == list(range(4000))

    def test_shuffle_changes_order_deterministically(self, ray_start):
        a = data.range(1000, parallelism=4).random_shuffle(seed=3).take_all()
        b = data.range(1000, parallelism=4).random_shuffle(seed=3).take_all()
        c = data.range(1000, parallelism=4).random_shuffle(seed=4).take_all()
        ids = lambda rows: [r["id"] for r in rows]  # noqa: E731
        assert ids(a) == ids(b)
        assert ids(a) != ids(c)
        assert ids(a) != list(range(1000))

    def test_repartition_distributed(self, ray_start):
        ds = data.range(999, parallelism=3).repartition(5)
        blocks = ds.materialize()
        assert blocks.num_blocks() == 5
        assert blocks.count() == 999

    def test_iter_batches_overlaps_produce_consume(self, ray_start,
                                                   tmp_path):
        """Judged by the order of events, not by the clock: the first
        block's task ends only after the consumer has its first batch."""
        import time as _t
        released = str(tmp_path / "released")

        def held(block):
            if 0 in block["id"]:
                deadline = _t.monotonic() + 120
                while not os.path.exists(released):
                    assert _t.monotonic() < deadline, "never released"
                    _t.sleep(0.02)
            return block

        ds = data.range(800, parallelism=8).map_batches(held)
        it = ds.iter_batches(batch_size=100)
        # iter_batches yields the first *completed* block (preserve_order=
        # False default) while the pipeline still runs: the held task
        # cannot head-of-line-block the consumer, and the batch is here
        # before that task can have ended.
        first = next(it)
        assert len(first["id"]) == 100 and 0 not in first["id"]
        with open(released, "w"):
            pass
        rest = list(it)
        assert sorted(int(i) for b in [first] + rest for i in b["id"]) == \
            list(range(800))

    def test_shuffle_after_map_fuses(self, ray_start):
        ds = (data.range(500, parallelism=4)
              .map_batches(lambda b: {"id": b["id"] * 2})
              .random_shuffle(seed=1))
        rows = sorted(r["id"] for r in ds.take_all())
        assert rows == [2 * i for i in range(500)]


class TestSortGroupby:
    """Distributed sort + groupby/aggregate (reference test analog:
    python/ray/data/tests/test_sort.py, test_all_to_all.py groupby)."""

    def test_sort_ascending_descending(self, ray_start):
        import numpy as np
        rng = np.random.default_rng(0)
        vals = rng.permutation(500).astype(np.int64)
        ds = from_numpy({"x": vals}, parallelism=6).sort("x")
        out = np.concatenate(
            [b["x"] for b in ds._blocks()
             if b and len(b.get("x", [])) > 0])
        np.testing.assert_array_equal(out, np.arange(500))
        ds2 = from_numpy({"x": vals}, parallelism=6).sort(
            "x", descending=True)
        out2 = np.concatenate(
            [b["x"] for b in ds2._blocks() if b and len(b.get("x", []))])
        np.testing.assert_array_equal(out2, np.arange(499, -1, -1))

    def test_sort_after_map_fuses_into_exchange(self, ray_start):
        import numpy as np
        ds = (ds_range(100, parallelism=4)
              .map_batches(lambda b: {"x": 99 - b["id"]})
              .sort("x"))
        out = np.concatenate([b["x"] for b in ds._blocks()
                              if b and len(b.get("x", []))])
        np.testing.assert_array_equal(out, np.arange(100))

    def test_groupby_aggregates(self, ray_start):
        import numpy as np
        n = 300
        ds = from_numpy({
            "k": np.arange(n) % 7,
            "v": np.arange(n, dtype=np.float64),
        }, parallelism=5)
        res = ds.groupby("k").aggregate(
            total=("v", "sum"), n=("v", "count"), avg=("v", "mean"),
            lo=("v", "min"), hi=("v", "max")).take_all()
        assert len(res) == 7
        by_key = {int(r["k"]): r for r in res}
        for k in _builtins_range(7):
            vals = np.arange(n)[np.arange(n) % 7 == k].astype(float)
            assert by_key[k]["total"] == pytest.approx(vals.sum())
            assert by_key[k]["n"] == len(vals)
            assert by_key[k]["avg"] == pytest.approx(vals.mean())
            assert by_key[k]["lo"] == vals.min()
            assert by_key[k]["hi"] == vals.max()

    def test_groupby_convenience_and_map_groups(self, ray_start):
        import numpy as np
        ds = from_items(
            [{"k": "a", "v": 1.0}, {"k": "b", "v": 2.0},
             {"k": "a", "v": 3.0}, {"k": "b", "v": 4.0},
             {"k": "c", "v": 5.0}], parallelism=3)
        counts = {r["k"]: r["count"] for r in ds.groupby("k").count()
                  .take_all()}
        assert counts == {"a": 2, "b": 2, "c": 1}
        means = {r["k"]: r["mean(v)"] for r in ds.groupby("k").mean("v")
                 .take_all()}
        assert means["a"] == pytest.approx(2.0)
        # map_groups: normalize within each group.
        normed = ds.groupby("k").map_groups(
            lambda b: {"k": b["k"], "v": b["v"] - b["v"].mean()}).take_all()
        got = sorted((r["k"], round(float(r["v"]), 3)) for r in normed)
        assert got == [("a", -1.0), ("a", 1.0), ("b", -1.0), ("b", 1.0),
                       ("c", 0.0)]

    def test_column_ops_and_unique(self, ray_start):
        import numpy as np
        ds = from_numpy({"a": np.arange(20), "b": np.arange(20) % 4,
                         "c": np.ones(20)}, parallelism=3)
        sel = ds.select_columns(["a", "b"]).take(1)[0]
        assert set(sel) == {"a", "b"}
        dropped = ds.drop_columns(["c"]).take(1)[0]
        assert set(dropped) == {"a", "b"}
        renamed = ds.rename_columns({"a": "x"}).take(1)[0]
        assert set(renamed) == {"x", "b", "c"}
        assert ds.unique("b") == [0, 1, 2, 3]
        # Renaming onto an existing column is data loss: reject it.
        with pytest.raises(Exception, match="duplicate target"):
            ds.rename_columns({"a": "b"}).take(1)

    def test_limit_and_union(self, ray_start):
        a = ds_range(50, parallelism=4)
        b = ds_range(10, parallelism=2)
        lim = a.limit(7)
        assert [r["id"] for r in lim.take_all()] == list(_builtins_range(7))
        u = a.union(b)
        assert u.count() == 60

    def test_writes_roundtrip(self, ray_start, tmp_path):
        import numpy as np
        ds = from_numpy({"x": np.arange(40),
                            "y": np.arange(40) * 2.0}, parallelism=3)
        pq_dir = str(tmp_path / "pq")
        files = ds.write_parquet(pq_dir)
        assert len(files) == 3
        back = Dataset.read_parquet(pq_dir + "/*.parquet")
        assert sorted(r["x"] for r in back.take_all()) == list(
            _builtins_range(40))
        csv_dir = str(tmp_path / "csv")
        ds.write_csv(csv_dir)
        back_csv = Dataset.read_csv(csv_dir + "/*.csv")
        assert back_csv.count() == 40
        json_dir = str(tmp_path / "js")
        ds.write_json(json_dir)
        import json as _json
        rows = []
        import glob as _glob
        for f in _glob.glob(json_dir + "/*.json"):
            with open(f) as fh:
                rows += [_json.loads(line) for line in fh if line.strip()]
        assert len(rows) == 40


import builtins as _bi
_builtins_range = _bi.range


class TestDatasources:
    """Binary / image / TFRecord readers (reference test analogs:
    python/ray/data/tests/test_image.py, test_tfrecords.py,
    test_binary.py)."""

    def test_read_binary_files(self, ray_start, tmp_path):
        for i in range(5):
            (tmp_path / f"f{i}.bin").write_bytes(bytes([i]) * (i + 1))
        ds = data.read_binary_files(str(tmp_path))
        rows = ds.take_all()
        assert len(rows) == 5
        sizes = sorted(len(r["bytes"]) for r in rows)
        assert sizes == [1, 2, 3, 4, 5]

    def test_read_images_map_iter_streams(self, ray_start, tmp_path):
        from PIL import Image
        import numpy as _np
        for i in range(8):
            arr = _np.full((12, 10, 3), i * 10, _np.uint8)
            Image.fromarray(arr).save(tmp_path / f"img{i}.png")
        (tmp_path / "notes.txt").write_text("ignored")

        ds = (data.read_images(str(tmp_path), size=(6, 5), mode="RGB")
              .map_batches(lambda b: {"image": b["image"].astype(
                  _np.float32) / 255.0, "path": b["path"]}))
        n = 0
        seen_means = []
        for batch in ds.iter_batches(batch_size=4):
            assert batch["image"].shape[1:] == (6, 5, 3)
            assert batch["image"].dtype == _np.float32
            n += len(batch["image"])
            seen_means.extend(batch["image"].mean(axis=(1, 2, 3)).tolist())
        assert n == 8
        assert max(seen_means) <= 1.0

    def test_tfrecord_roundtrip(self, ray_start, tmp_path):
        import numpy as _np
        cols = {
            "idx": _np.arange(50, dtype=_np.int64),
            "score": _np.linspace(0, 1, 50).astype(_np.float32),
            "name": _np.asarray([f"row-{i}" for i in range(50)], object),
        }
        out = str(tmp_path / "records")
        data.from_numpy(cols, parallelism=3).write_tfrecord(out)
        import glob as g
        files = g.glob(out + "/*.tfrecord")
        assert len(files) >= 1

        back = data.read_tfrecord(out, verify_crc=True)
        rows = back.take_all()
        assert len(rows) == 50
        by_idx = sorted(rows, key=lambda r: int(r["idx"]))
        assert int(by_idx[0]["idx"]) == 0 and int(by_idx[-1]["idx"]) == 49
        assert abs(float(by_idx[-1]["score"]) - 1.0) < 1e-6
        assert bytes(by_idx[7]["name"]).decode() == "row-7"

    def test_tfrecord_example_codec(self):
        from ray_tpu.data.datasource import decode_example, encode_example
        import numpy as _np
        payload = encode_example({
            "a": _np.asarray([1, -2, 3], _np.int64),
            "b": _np.asarray([0.5, 1.5], _np.float32),
            "c": b"blob", "d": "text",
        })
        out = decode_example(payload)
        _np.testing.assert_array_equal(out["a"], [1, -2, 3])
        _np.testing.assert_allclose(out["b"], [0.5, 1.5])
        assert out["c"] == [b"blob"] and out["d"] == [b"text"]

    def test_crc32c_known_vectors(self):
        from ray_tpu.data.datasource import crc32c
        # RFC 3720 test vectors.
        assert crc32c(b"") == 0
        assert crc32c(b"\x00" * 32) == 0x8A9136AA
        assert crc32c(b"\xff" * 32) == 0x62A8AB43
        assert crc32c(bytes(_builtin_range(32))) == 0x46DD794E


def _builtin_range(n):
    import builtins
    return builtins.range(n)


class TestBackpressure:
    def test_window_adapts_to_block_size(self):
        from ray_tpu.data.context import DataContext
        from ray_tpu.data.executor import _OpBackpressure

        ctx = DataContext.get()
        bp = _OpBackpressure()
        assert bp.window() == ctx.initial_in_flight
        # Huge blocks: window shrinks to the floor.
        bp._ema = float(ctx.op_memory_budget_bytes)
        assert bp.window() == ctx.min_in_flight
        # Tiny blocks: window grows to the cap.
        bp._ema = 1024.0
        assert bp.window() == ctx.max_in_flight

    def test_streaming_in_flight_bounded_by_budget(self, ray_start):
        """read -> map -> iter with per-op backpressure: once a block's
        size is observed (~2 MiB vs a 4 MiB budget), at most 2 tasks are
        in flight even though 16 blocks and 4 CPUs are available.
        (Store bytes are no proxy here: consumed blocks stay pinned until
        their zero-copy views are GC'd.)"""
        import threading
        import time as _t

        import numpy as _np
        from ray_tpu._private.runtime import driver_runtime
        from ray_tpu.data.context import DataContext

        ctx = DataContext.get()
        old = (ctx.op_memory_budget_bytes, ctx.initial_in_flight)
        ctx.op_memory_budget_bytes = 4 << 20  # 4 MiB budget
        ctx.initial_in_flight = 2
        try:
            def big_block(b):
                n = len(b["id"])
                return {"payload": _np.ones((n, 64 * 1024), _np.float64),
                        "id": b["id"]}  # ~2 MiB per block

            ds = data.range(64, parallelism=16).map_batches(big_block)
            rt = driver_runtime()
            peak = [0]
            stop = [False]

            def sampler():
                while not stop[0]:
                    with rt._running_lock:
                        peak[0] = max(peak[0], len(rt._running))
                    _t.sleep(0.002)

            t = threading.Thread(target=sampler, daemon=True)
            t.start()
            n = 0
            for batch in ds.iter_batches(batch_size=4):
                n += len(batch["id"])
                _t.sleep(0.01)  # slow consumer: backpressure must hold
            stop[0] = True
            t.join(timeout=5)
            assert n == 64
            # initial window 2; after the first observation the window is
            # budget/ema = 2.  Allow +1 for the submit/complete race.
            assert peak[0] <= 3, f"max in-flight tasks {peak[0]}"
        finally:
            (ctx.op_memory_budget_bytes, ctx.initial_in_flight) = old


class TestArrowInterop:
    """Arrow at the edges (reference: ray.data from_arrow/to_arrow_refs,
    arrow_block.py) — blocks stay numpy dicts (the device-feed format),
    Arrow converts zero-copy at the boundary."""

    def test_from_arrow_roundtrip(self, ray_start):
        import numpy as np
        import pyarrow as pa
        t = pa.table({"a": np.arange(100), "b": np.arange(100) * 2.0})
        ds = data.from_arrow(t, parallelism=4)
        rows = ds.take_all()
        assert len(rows) == 100
        assert rows[3] == {"a": 3, "b": 6.0}
        tables = [pa.table({"x": [1, 2]}), pa.table({"x": [3]})]
        ds2 = data.from_arrow(tables)
        assert sorted(r["x"] for r in ds2.take_all()) == [1, 2, 3]

    def test_to_arrow_refs_through_tasks(self, ray_start):
        import pyarrow as pa
        ds = data.range(50, parallelism=5).map_batches(
            lambda b: {"id": b["id"] + 1})
        refs = ds.to_arrow_refs()
        tables = ray_tpu.get(refs, timeout=120)
        assert all(isinstance(t, pa.Table) for t in tables)
        ids = sorted(i for t in tables for i in t.column("id").to_pylist())
        assert ids == list(range(1, 51))

    def test_iter_batches_formats(self, ray_start):
        import pyarrow as pa
        ds = data.range(40, parallelism=2)
        arrow_batches = list(ds.iter_batches(batch_size=10,
                                             batch_format="pyarrow"))
        assert all(isinstance(b, pa.Table) for b in arrow_batches)
        assert sum(b.num_rows for b in arrow_batches) == 40
        pdf = next(iter(ds.iter_batches(batch_size=10,
                                        batch_format="pandas")))
        assert list(pdf.columns) == ["id"] and len(pdf) == 10


class TestArrowBlocks:
    """block_format="arrow": pyarrow Tables as the physical block layout
    (reference: _internal/arrow_block.py) — parquet scans stay zero-copy
    through slice/batch, with numpy materialized only at the consumer
    boundary."""

    @pytest.fixture()
    def arrow_ctx(self):
        from ray_tpu.data.context import DataContext
        ctx = DataContext.get()
        old = ctx.block_format
        ctx.block_format = "arrow"
        yield ctx
        ctx.block_format = old

    def test_parquet_roundtrip_zero_copy(self, ray_start, arrow_ctx,
                                         tmp_path):
        import pyarrow as pa
        import pyarrow.parquet as pq

        from ray_tpu import data as rd

        t = pa.table({"x": np.arange(1000, dtype=np.int64),
                      "y": np.arange(1000, dtype=np.float64) * 0.5})
        pq.write_table(t, str(tmp_path / "a.parquet"))
        ds = rd.read_parquet(str(tmp_path / "a.parquet"))
        blocks = [b for b in ds.iter_batches(batch_size=300,
                                             batch_format="pyarrow")]
        assert all(isinstance(b, pa.Table) for b in blocks)
        assert sum(b.num_rows for b in blocks) == 1000
        # Zero-copy property (checked driver-locally, where buffer
        # identity survives): batch slices of a Table-block dataset
        # share the SOURCE table's buffers — same address, no copies.
        local = rd.from_arrow(t)
        batches = list(local.iter_batches(batch_size=300,
                                          batch_format="pyarrow"))
        src_addr = t.column("x").chunks[0].buffers()[1].address
        for b in batches:
            assert b.column("x").chunks[0].buffers()[1].address \
                == src_addr

    def test_numpy_only_at_consumer_boundary(self, ray_start, arrow_ctx,
                                             tmp_path):
        import pyarrow as pa
        import pyarrow.parquet as pq

        from ray_tpu import data as rd

        pq.write_table(pa.table({"x": np.arange(64, dtype=np.int32)}),
                       str(tmp_path / "b.parquet"))
        ds = rd.read_parquet(str(tmp_path / "b.parquet"))
        batches = list(ds.iter_batches(batch_size=16))
        assert all(isinstance(b, dict) for b in batches)
        assert all(isinstance(v, np.ndarray)
                   for b in batches for v in b.values())
        total = np.concatenate([b["x"] for b in batches])
        assert sorted(total.tolist()) == list(range(64))

    def test_transforms_on_arrow_blocks(self, ray_start, arrow_ctx,
                                        tmp_path):
        import pyarrow as pa
        import pyarrow.parquet as pq

        from ray_tpu import data as rd

        pq.write_table(pa.table({"k": np.repeat([0, 1], 50),
                                 "v": np.arange(100, dtype=np.float64)}),
                       str(tmp_path / "c.parquet"))
        ds = rd.read_parquet(str(tmp_path / "c.parquet"))
        doubled = ds.map_batches(lambda b: {"k": b["k"], "v": b["v"] * 2})
        agg = doubled.groupby("k").mean("v").take_all()
        by_k = {int(r["k"]): r["mean(v)"] for r in agg}
        assert by_k[0] == pytest.approx(np.arange(50).mean() * 2)
        assert by_k[1] == pytest.approx(np.arange(50, 100).mean() * 2)

    def test_arrow_blocks_survive_remote_execution(self, ray_start,
                                                   arrow_ctx, tmp_path):
        """The driver's block_format must reach spawned READ tasks
        (workers have a fresh default DataContext): blocks flowing into
        stages must really be pyarrow Tables, not silently numpy."""
        import pyarrow as pa
        import pyarrow.parquet as pq

        from ray_tpu import data as rd

        pq.write_table(pa.table({"x": np.arange(128, dtype=np.int64)}),
                       str(tmp_path / "d.parquet"))
        ds = rd.read_parquet(str(tmp_path / "d.parquet"))
        seen = ds.map_batches(
            lambda b: {"mod": np.array([type(b).__module__])},
            batch_format="block").take_all()
        assert all(r["mod"].startswith("pyarrow") for r in seen), seen

    def test_column_ops_on_arrow_blocks(self, ray_start, arrow_ctx,
                                        tmp_path):
        import pyarrow as pa
        import pyarrow.parquet as pq

        from ray_tpu import data as rd

        pq.write_table(pa.table({"x": np.arange(10, dtype=np.int64),
                                 "y": np.ones(10)}),
                       str(tmp_path / "e.parquet"))
        ds = rd.read_parquet(str(tmp_path / "e.parquet"))
        rows = ds.add_column("z", lambda b: b["x"] * 3) \
                 .rename_columns({"y": "w"}) \
                 .drop_columns(["w"]) \
                 .select_columns(["x", "z"]).take_all()
        assert rows[3] == {"x": 3, "z": 9}
        assert ds.unique("x") == list(range(10))
