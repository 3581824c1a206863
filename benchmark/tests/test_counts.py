"""The count functions on hand-worked shapes."""
import json
import os

import pytest

from benchmark.harness import counts, peaks
from benchmark.stacks import gpt
from benchmark.work import flash_train, paged_decode

CFG = json.load(open(os.path.join(
    os.path.dirname(__file__), "..", "configs", "cerebras-gpt-590m.json")))
TINY = dict(n_embd=8, n_inner=16, n_layer=1, n_head=2, vocab_size=10)


def test_gpt_matmul_params_by_hand():
    # one layer: 1536x4608 + 1536x1536 + 2 x 1536x6144 = 28,311,552
    assert 1536 * 4608 + 1536 * 1536 + 2 * 1536 * 6144 == 28_311_552
    d = gpt.dims(CFG)
    assert d["matmul_params"] == 18 * 28_311_552 + 1536 * 50257
    assert (d["layers"], d["heads"], d["kv_heads"], d["head_dim"]) == (
        18, 12, 12, 128)


def test_train_flops_per_token_590m():
    # 2 x 586.8M per token forward, attention 18 x 12 x 4 x 128 x 1024.5
    fwd = 2 * gpt.dims(CFG)["matmul_params"] + 18 * 12 * 4 * 128 * 1024.5
    per_token = counts.train_flops_per_token(
        gpt.forward_flops(CFG, 2048), 2048)
    assert per_token == pytest.approx(3 * fwd)
    assert per_token == pytest.approx(3.86e9, rel=0.01)


def test_forward_flops_decode_token_sees_its_context():
    p = gpt.dims(TINY)["matmul_params"]
    assert p == 8 * 24 + 64 + 2 * 128 + 80
    # one new token after 5: 6 keys, 2 heads, 4 * dh(4) each
    assert gpt.forward_flops(TINY, 1, 5) == 2 * p + 2 * 16 * 6
    # three tokens from nothing: 1 + 2 + 3 pairs
    assert gpt.forward_flops(TINY, 3) == 6 * p + 2 * 16 * 6


def test_serve_flops_by_hand():
    p = gpt.dims(TINY)["matmul_params"]
    # a prompt of 3: the head (2 x 8 x 10 a position) on the last one only
    assert gpt.prefill_flops(TINY, 3) == \
        gpt.forward_flops(TINY, 3) - 2 * 80 * 2
    # two streams whose contexts hold 6 and 4 keys, one new token each
    assert gpt.decode_flops(TINY, 2, 10) == \
        gpt.forward_flops(TINY, 1, 5) + gpt.forward_flops(TINY, 1, 3)
    assert gpt.decode_flops(TINY, 2, 10) == 4 * p + 2 * 16 * 10


def test_flash_train_by_hand():
    flops, nbytes = flash_train.count(4, 12, 12, 2048, 128)
    fwd = 4 * 4 * 12 * 2048 * 2048 * 128 / 2
    assert flops == 3.5 * fwd
    t = 4 * 12 * 2048 * 128 * 2
    assert nbytes == 4 * t + 8 * t
    least, bound = counts.roofline_seconds(flops, nbytes,
                                           peaks.peaks_for("TPU v5 lite"))
    assert bound == "compute" and least == pytest.approx(flops / 197e12)


def test_paged_decode_is_memory_bound():
    flops, nbytes = paged_decode.count(64000, 128, 12, 12, 128)
    assert nbytes == 2 * 12 * 128 * 2 * 64000 + 2 * 128 * 12 * 128 * 2
    assert flops == 4 * 12 * 128 * 64000
    _, bound = counts.roofline_seconds(flops, nbytes,
                                       peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory"


def test_a_kernel_metric_names_its_count_and_its_reader_by_file():
    """What a later PR adds for a new kernel: a metric file naming a
    `work` module and a reader, no edit of a file that is there."""
    from benchmark.harness import spec

    here = os.path.join(os.path.dirname(__file__), "..", "metrics")
    for name in sorted(os.listdir(here)):
        params = json.load(open(os.path.join(here, name)))
        assert hasattr(spec.load_by_name("readers", params["reader"]), "read")
        if "work" in params:
            assert hasattr(spec.load_by_name("work", params["work"]), "work")


def test_unknown_chip_is_an_error():
    with pytest.raises(LookupError):
        peaks.peaks_for("TPU v9000")
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
