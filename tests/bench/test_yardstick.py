"""The benchmark's arithmetic on hand-worked values: FLOP and byte
counts, the peaks table, the traffic generator and the quantiles."""
import json

import numpy as np
import pytest

from bench import roofline, traffic
from bench.cell import ROOT, load_cell
from bench.stats import meets, quantile


def _config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def test_layer_weights_pixtral():
    # wq + wo: 2 * 5120 * 4096; wk + wv: 2 * 5120 * 1024; MLP 3 * 5120 * 14336
    assert roofline.layer_weights(_config("pixtral-12b")) == 272_629_760


def test_prefill_flops_pixtral_image_chunk():
    # per layer: 1024 rows * 2 * 272,629,760 + 4 * 32 * 128 * (1024*1025/2)
    # = 558,345,748,480 + 8,598,323,200; 16 layers; head 2 * 5120 * 131072
    got = roofline.prefill_flops(_config("pixtral-12b"), [(0, 1024)])
    assert got == 16 * (558_345_748_480 + 8_598_323_200) + 1_342_177_280


def test_decode_flops_pixtral():
    # one lane after 1100 cached tokens: 16 layers of
    # 2 * 272,629,760 weights + 4 * 32 * 128 * 1101, plus 2 * 5120 * 131072
    got = roofline.decode_flops(_config("pixtral-12b"), [1100])
    assert got == 16 * (2 * 272_629_760 + 16_384 * 1101) + 1_342_177_280


def test_attention_kernel_costs():
    cfg = _config("pixtral-12b")
    # decode: 3001 keys/values of 8 heads * 128 * 2 bytes each, read once;
    # the query and output rows of 32 heads * 128 * 2 bytes
    flops, nbytes = roofline.decode_attn_cost(cfg, [3000])
    assert flops == 16_384 * 3001
    assert nbytes == 3001 * 4096 + 2 * 32 * 128 * 2
    # prefill: a 64-row text chunk after 1024 image rows
    flops, nbytes = roofline.prefill_attn_cost(_config("pixtral-12b"),
                                               [(1024, 64)])
    assert flops == 16_384 * (64 * 1024 + 64 * 65 // 2)
    assert nbytes == 1088 * 4096 + 64 * 2 * 32 * 128 * 2


def test_bound_is_the_larger_of_compute_and_bandwidth():
    peak = roofline.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    assert roofline.bound_seconds(197e12, 0, peak) == 1.0
    assert roofline.bound_seconds(0, 819e9, peak) == 1.0
    assert roofline.bound_seconds(197e12, 2 * 819e9, peak) == 2.0


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("cpu")


def test_quantile_nearest_rank_and_limits():
    xs = list(range(1, 11))
    assert quantile(xs, 0.9) == 9 and quantile(xs, 0.5) == 5
    assert quantile([3.0], 0.9) == 3.0
    lim = {"ttft_ms": 100, "tpot_ms": 10}
    assert meets(0.1, [0.01] * 9 + [0.5], lim)          # 90% of gaps within
    assert not meets(0.1, [0.01] * 8 + [0.5] * 2, lim)
    assert not meets(0.101, [], lim)


@pytest.mark.parametrize("cell", ["pixtral-12b.vqa-short"])
def test_traffic_same_seed_same_inputs_and_same_work_for_every_seed(cell):
    c = load_cell(cell)
    kw = dict(vocab=512, image_tokens=8, d_model=16)
    a = traffic.make_window(c.traffic, 2**31 + 77, 30, **kw)
    b = traffic.make_window(c.traffic, 2**31 + 77, 30, **kw)
    other = traffic.make_window(c.traffic, 5, 30, **kw)
    assert [q.due for q in a] == [q.due for q in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert all(np.array_equal(x.image, y.image) for x, y in zip(a, b))
    # another seed: the same sizes and gaps, in another order
    assert sorted(len(q.prompt) for q in a) == \
        sorted(len(q.prompt) for q in other)
    assert sorted(q.max_tokens for q in a) == \
        sorted(q.max_tokens for q in other)
    assert [len(q.prompt) for q in a] != [len(q.prompt) for q in other]
    # and the same arrival schedule
    assert [q.due for q in a] == [q.due for q in other]
    assert len(a) == round(c.traffic["arrivals"]["rate_per_s"] * 30)
    assert a[0].due == 0 and max(q.due for q in a) < 30
    gaps = np.diff([q.due for q in a])
    assert gaps.min() > 0 and gaps.max() > 3 * gaps.min()   # not uniform
