"""Each kernel's operation and byte count, and the model FLOPs per call,
against a count made by hand."""

import bench_tiny  # noqa: F401
import pytest

from bench import catalog
from bench.families import dense

M = {"L": 2, "H": 4, "K": 2, "dh": 8}


def test_paged_decode_attention_counts_valid_context_only():
    rl = catalog.Catalog().roofline("paged_decode_attention")
    f, b = rl.work(("decode", [3, 10], 2), M)
    # per layer: 4*H*dh per key = 128 per key, 13 keys
    assert f == 2 * 128 * 13
    # per layer: K and V of 13 keys, 2 kv heads x 8 x 2 B each = 32 B a row
    # per key and tensor, plus q in and o out of 4 x 8 x 2 B per lane
    assert b == 2 * (13 * 2 * 32 + 2 * (2 * 64))


def test_flash_attention_counts_the_causal_pairs():
    rl = catalog.Catalog().roofline("flash_attention")
    f, b = rl.work(("prefill", 4, 3, True), M)
    # queries at 4,5,6 see 5,6,7 keys = 18 pairs
    assert f == 2 * 4 * 4 * 8 * 18
    # q and o: 3 x 4 x 8 each; K and V: 7 x 2 x 8 each; bf16
    assert b == 2 * (2 * 3 * 32 + 2 * 7 * 16) * 2


@pytest.mark.parametrize("final", [True, False])
def test_model_flops_of_a_prefill_chunk(final):
    conf = dict(bench_tiny.TINY_DENSE, name="tiny")
    d, f, V, L, H, dh = 96, 256, 512, 2, 6, 16
    per_layer = d * H * dh * 2 + d * 2 * dh * 2 + 3 * d * f   # q,o k,v mlp
    assert dense.param_count(conf, embedding=False) == L * (per_layer + 2 * d) + d
    dense_flops = 2 * dense.param_count(conf, embedding=False)
    got = dense.call_flops(conf, ("prefill", 10, 2, final))
    want = 2 * dense_flops + 4 * L * H * dh * (11 + 12) + (2 * d * V if final else 0)
    assert got == want


def test_model_flops_of_a_decode_call():
    conf = dict(bench_tiny.TINY_DENSE, name="tiny")
    dense_flops = 2 * dense.param_count(conf, embedding=False)
    got = dense.call_flops(conf, ("decode", [5, 7], 2))
    assert got == 2 * (dense_flops + 2 * 96 * 512) + 4 * 2 * 6 * 16 * 12


def test_peaks_refuse_an_unknown_device():
    from bench import peaks

    assert peaks.lookup("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.lookup("TPU v99")
