"""The operation and byte functions against the program's own count and
against hand counts."""

import pytest

from benchmark import opcount
from benchmark.manifest import Manifest
from benchmark.peaks import device_peaks


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


@pytest.mark.parametrize("batch,seq", [(32, 128), (32, 512), (128, 128)])
def test_bert_matches_bench_py(manifest, batch, seq):
    from bench import bert_train_flops_per_step

    cfg = manifest.config("bert-base")
    per_token = opcount.train_flops_per_token("bert", cfg, seq)
    assert per_token * batch * seq == pytest.approx(
        bert_train_flops_per_step(batch, seq), rel=1e-12)


def test_mistral_by_hand(manifest):
    cfg = dict(manifest.config("mistral-7b-l16"), num_hidden_layers=1)
    # one layer: q 4096x4096, k and v 4096x1024, o 4096x4096, three 4096x14336
    layer = 4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 14336
    head = 4096 * 32000
    seq = 8
    pairs = 8 * 9 // 2
    want = 2 * seq * (layer + head) + 4 * 32 * 128 * pairs
    assert opcount.decoder_forward_flops_per_sequence(cfg, seq) == want
    assert opcount.train_flops_per_token("mistral", cfg, seq) == pytest.approx(3 * want / seq)


@pytest.mark.parametrize("seq,window,want", [
    (4, None, 10), (4, 8, 10), (4, 4, 10), (6, 2, 1 + 2 * 5), (8192, 4096, 4096 * 4097 // 2 + 4096 * 4096),
])
def test_causal_window_pairs(seq, window, want):
    assert opcount.causal_pairs(seq, window) == want
    brute = sum(min(i + 1, window or seq) for i in range(seq))
    assert want == brute


def test_full_depth_parameter_counts(manifest):
    """2·S·P per sequence: the count implies Mistral-7B's 7.24 B and
    BERT-base's 110 M parameters (less the embeddings, which do no matrix
    work)."""
    m = dict(manifest.config("mistral-7b-l16"), num_hidden_layers=32, sliding_window=None)
    p = (opcount.decoder_forward_flops_per_sequence(m, 1) - 4 * 32 * 32 * 128) / 2
    assert p == pytest.approx(7.24e9 - 32000 * 4096, rel=2e-3)
    b = manifest.config("bert-base")
    p = (opcount.bert_forward_flops_per_sequence(b, 1) - 4 * 12 * 768) / 2
    assert p == pytest.approx(85.0e6 + 24.0e6, rel=1e-2)


def test_attention_cost_and_roofline(manifest):
    cfg = manifest.config("bert-base")
    cost = opcount.attention_train_cost("bert", cfg, seq=512, batch=32)
    fwd = 4 * 512 * 512 * 64 * 12                      # QK^T and PV, 12 heads of 64
    assert cost["flops"] == pytest.approx(32 * 12 * 3.5 * fwd)
    elems = 512 * 12 * 64
    assert cost["bytes"] == 32 * 12 * 2 * (4 * elems + 8 * elems)
    peaks = device_peaks("TPU v5 lite")
    t, bound = opcount.roofline_seconds(cost["flops"], cost["bytes"], peaks)
    assert bound == "compute" and t == pytest.approx(cost["flops"] / 197e12)
    assert opcount.roofline_seconds(1.0, 819e9, peaks) == (pytest.approx(1.0), "memory")


def test_an_unknown_device_is_an_error_not_a_default():
    with pytest.raises(RuntimeError, match="no published peaks"):
        device_peaks("cpu")


@pytest.mark.parametrize("family,config,seq,batch", [
    ("bert", "bert-base", 512, 32), ("bert", "bert-base", 128, 8),
    ("mistral", "mistral-7b-l12-x4", 4096, 4), ("mistral", "mistral-7b-l16", 8192, 1),
])
def test_the_family_modules_return_what_the_literal_dict_did(manifest, family, config, seq, batch):
    """``opcount`` asks the family module (``forward_flops_per_sequence``,
    ``attention_pairs``); before, it looked the family up in a dict and an
    ``if``. A new family adds a file and edits none."""
    cfg = manifest.config(config)
    forward = {"bert": opcount.bert_forward_flops_per_sequence,
               "mistral": opcount.decoder_forward_flops_per_sequence}[family]
    assert opcount.train_flops_per_token(family, cfg, seq) == 3.0 * forward(cfg, seq) / seq
    pairs = seq * seq if family == "bert" else opcount.causal_pairs(seq, cfg["sliding_window"])
    heads = cfg["num_attention_heads"]
    d = cfg["hidden_size"] // heads
    cost = opcount.attention_train_cost(family, cfg, seq, batch)
    assert cost["flops"] == batch * cfg["num_hidden_layers"] * 3.5 * (4.0 * pairs * d * heads)
    assert cost["bytes"] == batch * cfg["num_hidden_layers"] * 2.0 * 12 * seq * heads * d


def test_serving_counts_one_token_at_the_mixs_mean_context(manifest):
    """``forward_flops_per_token``: the matrix products of one token once,
    and attention against the mean number of keys a token of the mix sees."""
    from benchmark.families import mistral

    cfg = manifest.config("mistral-7b-l16")
    layer = 4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 14336
    products = 2 * (16 * layer + 4096 * 32000)
    assert opcount.decoder_forward_flops_per_token(cfg, 1) == products + 4 * 16 * 32 * 128
    assert opcount.decoder_forward_flops_per_token(cfg, 300.5) == products + 4 * 16 * 32 * 128 * 300.5
    # one prompt length, one output length: 100 + 50 - 1 tokens are fed, the
    # i-th against i keys, and the window never bites
    fixed = lambda n: {"dist": "lognormal", "median": n, "sigma": 0.0, "min": n, "max": n}
    got = mistral.serve_context(cfg, {"prompt_tokens": fixed(100), "output_tokens": fixed(50)}, None)
    assert got["mean_context_tokens"] == pytest.approx(150 / 2)
    assert got["forward_flops_per_token"] == opcount.decoder_forward_flops_per_token(cfg, 75.0)
    # the cell's own mix: a constant of the mix, whatever the seed
    cell = mistral.serve_context(cfg, manifest.traffic("gen-closed"), cfg["serve"])
    assert 250 < cell["mean_context_tokens"] < 320
    assert cell["forward_flops_per_token"] == pytest.approx(7.316e9, rel=1e-3)
