"""Slot data-feed pipeline: DataGenerator -> MultiSlot protocol ->
MultiSlotDataFeed batching -> Executor.train_from_dataset.

Reference: framework/data_feed.cc (MultiSlotDataFeed),
fleet/data_generator/data_generator.py, base/executor.py:3222
train_from_dataset.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.fleet import MultiSlotDataGenerator
from paddle_tpu.distributed.ps.dataset import (
    InMemoryDataset, MultiSlotDataFeed, QueueDataset, batch_iterator,
)


class _CtrGen(MultiSlotDataGenerator):
    """words (varlen int ids) + label (1 int)."""

    def generate_sample(self, line):
        def gen():
            ids, label = line
            yield [("words", [str(i) for i in ids]), ("label", [str(label)])]

        return gen


def _protocol_file(tmp_path, rows):
    gen = _CtrGen()
    lines = []
    for row in rows:
        for parsed in gen.generate_sample(row)():
            lines.append(gen._gen_str(parsed))
    path = tmp_path / "part-0.txt"
    path.write_text("".join(lines))
    return str(path)


ROWS = [([3, 7, 9], 1), ([4], 0), ([5, 5], 1), ([8, 1, 2, 6], 0),
        ([2, 2], 1)]


class TestMultiSlotProtocol:
    def test_generator_roundtrip(self, tmp_path):
        path = _protocol_file(tmp_path, ROWS)
        first = open(path).readline().strip()
        assert first == "3 3 7 9 1 1"

    def test_parse_and_collate_varlen(self, tmp_path):
        feed = MultiSlotDataFeed([("words", "int64"), ("label", "int64")])
        path = _protocol_file(tmp_path, ROWS)
        ds = QueueDataset()
        ds.init(batch_size=2)
        ds.set_filelist([path])
        batches = list(batch_iterator(ds, feed, batch_size=2))
        assert len(batches) == 3  # 5 rows, bs 2, keep last
        b0 = batches[0]
        # varlen slot padded + length vector
        np.testing.assert_array_equal(b0["words"], [[3, 7, 9], [4, 0, 0]])
        np.testing.assert_array_equal(b0["words.lens"], [3, 1])
        np.testing.assert_array_equal(b0["label"], [[1], [0]])

    def test_parse_errors_surface(self):
        feed = MultiSlotDataFeed(["words", "label"])
        with pytest.raises(ValueError, match="declared"):
            feed.parse_line("3 1 2")  # slot claims 3 values, has 2
        with pytest.raises(ValueError, match="trailing"):
            feed.parse_line("1 5 1 0 99")

    def test_inmemory_shuffle_preserves_rows(self, tmp_path):
        path = _protocol_file(tmp_path, ROWS)
        ds = InMemoryDataset()
        ds.init(batch_size=2)
        ds.set_filelist([path])
        ds.load_into_memory()
        assert ds.get_memory_data_size() == 5
        ds.local_shuffle()
        feed = MultiSlotDataFeed(["words", "label"])
        total = sum(len(b["label"]) for b in batch_iterator(ds, feed))
        assert total == 5


class TestTrainFromDataset:
    def test_executor_trains_from_slot_dataset(self, tmp_path):
        import paddle_tpu.static as static

        path = _protocol_file(tmp_path, ROWS)
        main = static.Program()
        startup = static.Program()
        with static.program_guard(main, startup):
            # dense label slot [B, 1]; embedding over padded word ids
            words = static.data("words", shape=[None, 3], dtype="int64")
            label = static.data("label", shape=[None, 1], dtype="int64")
            emb = static.nn.embedding(words, size=[32, 8])
            feat = emb.sum(axis=1)
            logit = static.nn.fc(feat, size=1)
            loss = ((logit - label.astype("float32")) ** 2).mean()

        exe = static.Executor()
        ds = InMemoryDataset()
        ds.init(batch_size=2, use_var=["words", "label"])
        ds.set_filelist([path])
        ds.load_into_memory()
        # only fixed-width batches match the placeholder [None, 3]: filter
        rows3 = [r for r in ROWS if len(r[0]) == 3]
        ds._samples = [l for l in ds._samples
                       if l.split()[0] == "3"]
        assert len(ds._samples) == len(rows3)
        results = exe.train_from_dataset(main, ds, fetch_list=[loss],
                                         print_period=0)
        assert results and np.isfinite(results[0][0]).all()

    def test_missing_feed_schema_raises(self):
        import paddle_tpu.static as static

        exe = static.Executor()
        ds = QueueDataset()
        ds.init(batch_size=2)  # no use_var -> no schema
        with pytest.raises(ValueError, match="data feed"):
            exe.train_from_dataset(None, ds)


class TestNativeParser:
    def test_native_matches_python_parser(self, tmp_path):
        from paddle_tpu import native

        if not native.is_available():
            pytest.skip("native toolchain unavailable")
        feed = MultiSlotDataFeed([("words", "int64"), ("score", "float32"),
                                  ("label", "int64")])
        lines = ["2 5 9 1 0.25 1 1\n", "3 1 2 3 2 0.5 1.5 1 0\n",
                 "1 7 1 2.0 1 1\n"]
        got = feed.collate_batch_lines(lines)
        want = feed.collate([feed.parse_line(l) for l in lines])
        assert set(got) == set(want)
        for k in want:
            np.testing.assert_allclose(got[k], want[k], err_msg=k)

    def test_native_parser_throughput(self, monkeypatch):
        """The point of the data_feed.cc analog, as counts (seconds on a
        CPU host that tier-1 shares among six workers say nothing): a
        large batch goes through the native parser in ONE call that
        returns every row and value, and the per-line Python token loop
        is not entered once; the Python path makes a call a line."""
        from paddle_tpu import native

        if not native.is_available():
            pytest.skip("native toolchain unavailable")
        rng = np.random.RandomState(0)
        lines, n_values = [], 0
        for _ in range(4000):
            n = rng.randint(1, 40)
            n_values += n
            ids = " ".join(str(v) for v in rng.randint(0, 10 ** 6, n))
            lines.append(f"{n} {ids} 1 {rng.randint(0, 2)}\n")
        feed = MultiSlotDataFeed([("words", "int64"), ("label", "int64")])

        calls = {"native": 0, "python": 0, "rows": 0, "values": 0}
        parse_multislot, parse_line = native.parse_multislot, feed.parse_line

        def counted_native(text, flags):
            out = parse_multislot(text, flags)
            calls["native"] += 1
            calls["rows"] += len(out[0][0])
            calls["values"] += int(out[0][0].sum())
            return out

        def counted_python(line):
            calls["python"] += 1
            return parse_line(line)

        monkeypatch.setattr(native, "parse_multislot", counted_native)
        monkeypatch.setattr(feed, "parse_line", counted_python)
        got = feed.collate_batch_lines(lines)
        assert calls == {"native": 1, "python": 0, "rows": len(lines),
                         "values": n_values}
        want = feed.collate([feed.parse_line(l) for l in lines])
        assert calls["python"] == len(lines) and calls["native"] == 1
        np.testing.assert_array_equal(got["words"], want["words"])

    def test_malformed_line_raises_with_line_number(self):
        feed = MultiSlotDataFeed(["a", "b"])
        with pytest.raises(ValueError):
            feed.collate_batch_lines(["1 5 1 3\n", "2 1\n"])
