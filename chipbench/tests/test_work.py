"""The required work of kernels (work.py) and of the dense block's steps
(references/dense.py) against shapes worked out by hand, for both
configs."""
import json

import pytest

from chipbench import model, spec, work
from chipbench.references import dense

STABLELM = dict(layers=2, d=2560, heads=32, kv_heads=32, head_dim=80,
                d_ff=6912, vocab=50304, gated=True, tied=False)
GRANITE = dict(layers=2, d=6144, heads=48, kv_heads=1, head_dim=128,
               d_ff=24576, vocab=49152, gated=False, tied=True)


def _conf(name):
    return json.loads((spec.HERE / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name,want", [("stablelm-3b-d2", STABLELM),
                                       ("granite-34b-d2", GRANITE)])
def test_config_dims(name, want):
    dm = dense.dims(_conf(name))
    assert {k: getattr(dm, k) for k in want} == want


def test_layer_params_by_hand():
    s = dense.Dims(**STABLELM, norm_eps=1e-5, rope_theta=1e4)
    # q, k, v, o: 4 x 2560 x 2560; SwiGLU: 3 x 2560 x 6912
    assert dense.layer_matmul_params(s) == 4 * 2560 * 2560 + 3 * 2560 * 6912
    g = dense.Dims(**GRANITE, norm_eps=1e-5, rope_theta=1e4)
    # q and o: 2 x 6144 x 6144; k and v: one head of 128; GELU: 2 matrices
    assert dense.layer_matmul_params(g) == (2 * 6144 * 6144 + 2 * 6144 * 128
                                           + 2 * 6144 * 24576)


def test_prefill_and_decode_flops_by_hand():
    g = dense.Dims(**GRANITE, norm_eps=1e-5, rope_theta=1e4)
    per_layer = 2 * 6144 * 6144 + 2 * 6144 * 128 + 2 * 6144 * 24576
    S = 4096
    want = (2 * S * 2 * per_layer + 2 * 6144 * 49152
            + 2 * 2 * 48 * 128 * S * (S + 1))
    assert dense.prefill_flops(g, S) == want
    # the head runs for the last position only, the embedding is a gather
    assert 6.6e12 < want < 6.7e12
    lens = [1024, 2048]
    want = (2 * 2 * (2 * per_layer + 6144 * 49152)
            + 2 * sum(4 * 48 * 128 * (n + 1) for n in lens))
    assert dense.decode_flops(g, lens) == want


def test_paged_attention_work_by_hand():
    g = dense.Dims(**GRANITE, norm_eps=1e-5, rope_theta=1e4)
    # length 31 attends 32 tokens: two 16-token pages of one head of 128
    # in bf16 for K and for V; q and out 48 x 128 bf16 each
    flops, nbytes = dense.paged_attention_work(g, [31], 16)
    assert nbytes == 2 * 2 * (16 * 128 * 2) + 2 * 48 * 128 * 2
    assert flops == 4 * 48 * 128 * 32
    # one more token needs a third page
    assert dense.paged_attention_work(g, [32], 16)[1] == \
        2 * 3 * (16 * 128 * 2) + 2 * 48 * 128 * 2


def test_page_moves_and_roofline_bound():
    assert work.page_move_bytes(10, 131072) == 2 * 10 * 131072
    pk = work.peaks("TPU v5 lite")
    assert work.least_seconds(0, 819e9, pk) == pytest.approx(1.0)
    assert work.least_seconds(197e12, 0, pk) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        work.peaks("TPU v4")


def test_param_pages_by_hand():
    # stablelm-3b-d2: two layers of 79.3 M, embedding and untied head of
    # 50304 x 2560, in bf16 pages of 32768 elements (64 KiB)
    for name, pages, page_bytes in (("stablelm-3b-d2", 12703, 65536),
                                    ("granite-34b-d2", 32355, 131072)):
        cfg = dense.arch(_conf(name))
        assert model.param_pages(cfg) == pages
        assert model.page_bytes(cfg) == page_bytes


# The counts the parent commit's work.py gave at published widths, before
# they moved into references/dense.py: the move changes none of them.
PARENT_COUNTS = {
    "granite-34b-d2": {
        "prefill": [195088613376, 1579029626880, 3208994881536, 6623544213504],
        "decode": [2126561280, 6713180160],
        "paged_attention": [(786432, 40960), (126468096, 2719744)]},
    "stablelm-3b-d2": {
        "prefill": [41027502080, 335812362240, 692842004480, 1471325798400],
        "decode": [577392640, 1871134720],
        "paged_attention": [(327680, 337920), (52695040, 52951040)]},
}


@pytest.mark.parametrize("name", sorted(PARENT_COUNTS))
def test_counts_equal_the_parents(name):
    conf = _conf(name)
    ref = spec.reference_module(conf)
    dm, want = ref.dims(conf), PARENT_COUNTS[name]
    assert [ref.prefill_flops(dm, n) for n in (128, 1024, 2048, 4096)] \
        == want["prefill"]
    assert [ref.decode_flops(dm, lens) for lens in ([128], [1024, 2048, 4097])] \
        == want["decode"]
    assert [ref.paged_attention_work(dm, lens, 16)
            for lens in ([31], [32, 1000, 4111])] == want["paged_attention"]
