"""Model FLOPs from the configurations, against counts by hand."""
import json

import pytest

from lib.registry import BENCH, Registry

F = Registry().model("gpt2")


def _cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def _traffic(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def test_gpt2_medium_round_by_hand():
    # per token and block: q,k,v,o 4*1024^2 + MLP 2*1024*4096 MACs
    blk = 2 * (4 * 1024 ** 2 + 2 * 1024 * 4096)          # 25,165,824
    attn = 4 * 1024 * (1024 * 1025 // 2)                 # causal, per seq
    vocab = 2 * 1024 * 50257                             # per token
    seq, n_seqs = 1024, 4 * 4
    fwd = lambda blocks: n_seqs * (blocks * (seq * blk + attn)
                                   + seq * vocab)
    want_client = 2 * fwd(6 + 3)
    want_server = 3 * fwd(24 - 6)
    got = F.fed_round_flops(_cfg("gpt2-medium"), _traffic("fed-s1024-b4"))
    assert got["client"] == want_client
    assert got["server"] == want_server
    assert got["total"] == pytest.approx(40.59e12, rel=1e-3)
    assert got["server"] / got["total"] == pytest.approx(0.719, abs=1e-3)


def test_gpt2_small_round_by_hand():
    blk = 2 * (4 * 768 ** 2 + 2 * 768 * 3072)
    attn = 4 * 768 * (128 * 129 // 2)
    vocab = 2 * 768 * 50257
    seq, n_seqs = 128, 4 * 32
    fwd = lambda blocks: n_seqs * (blocks * (seq * blk + attn)
                                   + seq * vocab)
    got = F.fed_round_flops(_cfg("gpt2-small"), _traffic("fed-s128-b32"))
    assert got["client"] == 2 * fwd(3 + 1)
    assert got["server"] == 3 * fwd(12 - 3)
    # the 50257-wide projection is the largest single share of the round
    proj = (2 + 3) * n_seqs * seq * vocab
    assert proj / got["total"] == pytest.approx(0.4, abs=0.05)


def test_decode_and_prefill_by_hand():
    cfg = _cfg("gpt2-medium")
    blk = 2 * (4 * 1024 ** 2 + 2 * 1024 * 4096)
    assert F.decode_token_flops(cfg, 100) == \
        24 * (blk + 4 * 1024 * 100) + 2 * 1024 * 50257
    assert F.prefill_flops(cfg, 64) == \
        24 * (64 * blk + 4 * 1024 * 64 * 65 // 2) + 2 * 1024 * 50257
