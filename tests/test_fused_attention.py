"""The fused dual-probe flash attention: one blocked online-softmax
pass over K/V carries both estimator streams (clean + ±mu-perturbed),
with two (m, l, acc) scratch sets sharing every K/V block load.  The
score perturbation is drawn from the same global-coordinate hash field
as the matmul kernels — block-size invariant, bit-identical across
interpret / xla, addressed at (h*Sq + q_pos, kv_pos) — so the server
can replay the weight directions from (seed, coeffs) alone while the
score probe stays a zero-mean phantom direction that is never
reconstructed (wk/wv leave the seed stream via attn_kv_seed_pred)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as FA
from repro.kernels import ops as O
from repro.kernels import ref
from repro.kernels.zo_matmul import uniform_noise

jax.config.update("jax_platform_name", "cpu")


def _qkv(B=2, Sq=32, H=4, Kv=2, D=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    mk = lambda k, *s: jax.random.normal(k, s, jnp.float32)
    return (mk(ks[0], B, Sq, H, D), mk(ks[1], B, Sq, H, D),
            mk(ks[2], B, Sq, Kv, D), mk(ks[3], B, Sq, Kv, D),
            mk(ks[4], B, Sq, Kv, D), mk(ks[5], B, Sq, Kv, D))


VARIANTS = [
    dict(causal=True),
    dict(causal=False),
    dict(causal=True, window=8),
    dict(causal=True, cap=5.0),
    dict(causal=True, window=8, cap=5.0),
]


@pytest.mark.parametrize("kw", VARIANTS)
@pytest.mark.parametrize("kv_heads", [4, 2, 1])
def test_fused_shared_kv_matches_ref(kw, kv_heads):
    """Score-probe mode (shared clean K/V) vs the pure-jnp oracle across
    causal x window x soft-cap x GQA group sizes."""
    qa, qb, k, v, _, _ = _qkv(Kv=kv_heads)
    H, Sq, Skv = qa.shape[2], qa.shape[1], k.shape[1]
    oa, ob = FA.zo_dual_flash_attention(
        qa, qb, k, v, seed=7, mu_a=0.0, mu_b=0.1, perturb_a=False,
        perturb_b=True, bq=16, bk=16, interpret=True, **kw)
    u = O.attn_score_field(7, H, Sq, Skv)
    ra, rb = ref.zo_dual_flash_attention_ref(
        qa, qb, k, v, u=u, mu_a=0.0, mu_b=0.1, perturb_a=False,
        perturb_b=True, **kw)
    np.testing.assert_allclose(np.asarray(oa), np.asarray(ra),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ob), np.asarray(rb),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kw", VARIANTS)
def test_clean_stream_bitmatches_single_flash(kw):
    """Static perturb flags keep the clean stream's op graph identical
    to the single-stream kernel — bitwise, not approximately."""
    qa, qb, k, v, kb, vb = _qkv()
    oa, _ = FA.zo_dual_flash_attention(
        qa, qb, k, v, seed=7, mu_b=0.1, perturb_b=True, bq=16, bk=16,
        interpret=True, **kw)
    fa = FA.flash_attention(qa, k, v, bq=16, bk=16, interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(oa), np.asarray(fa))
    # weights mode (per-stream K/V, no score noise): both streams
    # bit-match their own separate flash pass
    oa2, ob2 = FA.zo_dual_flash_attention(
        qa, qb, k, v, kb=kb, vb=vb, perturb_a=False, perturb_b=False,
        bq=16, bk=16, interpret=True, **kw)
    fb = FA.flash_attention(qb, kb, vb, bq=16, bk=16, interpret=True,
                            **kw)
    np.testing.assert_array_equal(np.asarray(oa2), np.asarray(fa))
    np.testing.assert_array_equal(np.asarray(ob2), np.asarray(fb))


# (Sq = Skv, bk) at bq 16, causal: whole (q, kv) blocks are masked.  53
# tokens have no block in [8, 16] or [16, 32]: the last block is padded.
# Compiled and interpret mode pick the same tiles for these shapes
DEAD_SHAPES = [(64, 16), (64, 32), (53, 16), (53, 32)]


@pytest.mark.parametrize("cap", [0.0, 5.0])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("probe", ["weights", "scores"])
@pytest.mark.parametrize("window", [0, 8, 20])
@pytest.mark.parametrize("seq,bk", DEAD_SHAPES)
def test_dual_flash_with_dead_blocks(seq, bk, window, probe, dtype, cap):
    """Shapes where the dual kernel skips blocks: both streams against
    the oracle, and each unperturbed stream bitwise against the
    single-stream kernel, which skips the same blocks (bf16 inputs:
    against it over their f32 values, rounded to bf16 at the end)."""
    live, total = FA.dual_live_blocks(seq, seq, 16, bk, True, window)
    assert live < total
    dt = jnp.dtype(dtype)
    qa, qb, k, v, kb, vb = (t.astype(dt) for t in
                            _qkv(B=1, Sq=seq, H=2, Kv=1, seed=3))
    kw = dict(causal=True, window=window, cap=cap)
    if probe == "weights":
        oa, ob = FA.zo_dual_flash_attention(
            qa, qb, k, v, kb=kb, vb=vb, perturb_b=False, bq=16, bk=bk,
            interpret=True, **kw)
        ra, rb = ref.zo_dual_flash_attention_ref(
            qa, qb, k, v, kb=kb, vb=vb, perturb_b=False, **kw)
        clean = [(oa, qa, k, v), (ob, qb, kb, vb)]
    else:
        oa, ob = FA.zo_dual_flash_attention(
            qa, qb, k, v, seed=7, mu_b=0.1, perturb_b=True, bq=16, bk=bk,
            interpret=True, **kw)
        ra, rb = ref.zo_dual_flash_attention_ref(
            qa, qb, k, v, u=O.attn_score_field(7, 2, seq, seq), mu_b=0.1,
            perturb_b=True, **kw)
        clean = [(oa, qa, k, v)]
    tol = dict(rtol=1e-5, atol=1e-5) if dt == jnp.float32 else dict(
        rtol=1e-2, atol=1e-3)
    for got, want in ((oa, ra), (ob, rb)):
        assert got.dtype == dt
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **tol)
    f32 = lambda t: t.astype(jnp.float32)
    for got, q, kk, vv in clean:
        single = FA.flash_attention(f32(q), f32(kk), f32(vv), bq=16, bk=bk,
                                    interpret=True, **kw)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(single.astype(dt)))


@pytest.mark.parametrize("probe", ["weights", "scores"])
def test_dual_flash_reads_no_dead_block(probe):
    """Keys past the last query are in blocks no query block sees.  NaN
    there would reach the outputs through a masked block's product
    (0 * NaN), so finite outputs equal to the oracle's over finite keys
    show that no dead block's K/V reaches the kernel's arithmetic."""
    Sq, Skv = 32, 64
    qa, qb, k, v, kb, vb = _qkv(B=1, Sq=Skv, H=2, Kv=1, seed=4)
    qa, qb = qa[:, :Sq], qb[:, :Sq]
    dead = lambda t: t.at[:, Sq:].set(jnp.nan)
    if probe == "weights":
        kws = dict(kb=kb, vb=vb, perturb_b=False)
        got = FA.zo_dual_flash_attention(
            qa, qb, dead(k), dead(v), kb=dead(kb), vb=dead(vb),
            perturb_b=False, bq=16, bk=16, interpret=True)
    else:
        kws = dict(u=O.attn_score_field(7, 2, Sq, Skv), mu_b=0.1)
        got = FA.zo_dual_flash_attention(
            qa, qb, dead(k), dead(v), seed=7, mu_b=0.1, bq=16, bk=16,
            interpret=True)
    want = ref.zo_dual_flash_attention_ref(qa, qb, k, v, **kws)
    assert FA.dual_live_blocks(Sq, Skv, 16, 16) == (3, 8)
    for g, w in zip(got, want):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 8),
                                           (True, 20), (False, 0),
                                           (False, 8)])
@pytest.mark.parametrize("sq,skv,bq,bk", [
    (64, 64, 16, 16), (64, 64, 16, 32), (53, 53, 16, 16),
    (53, 53, 16, 32), (32, 64, 16, 16), (64, 32, 32, 16)])
def test_dual_live_blocks_counts_unmasked_blocks(sq, skv, bq, bk, causal,
                                                 window):
    """The helper's count against a brute-force one: the blocks in
    which the kernel's mask has a true entry."""
    p = FA._dual_plan(sq, skv, bq, bk, causal, window, 0.0, 1.0, False)
    brute = sum(bool(FA._mask(p, qi, ki, transposed=False).any())
                for qi in range(p.nq) for ki in range(p.nk))
    assert FA.dual_live_blocks(sq, skv, bq, bk, causal,
                               window) == (brute, p.nq * p.nk)


@pytest.mark.parametrize("seq,blocks", [(8192, (136, 256)),
                                        (1024, (3, 4)), (128, (1, 1))])
def test_dual_live_blocks_of_the_cells(seq, blocks):
    """What the chip runs at the fed cells' lengths (Moonlight, GPT-2
    Medium, GPT-2 Small) on 512-token tiles: a sequence within one tile
    has no dead block."""
    assert FA.dual_live_blocks(seq, seq) == blocks


GRAD_CASES = {
    # 40 tokens on 16-row tiles: the last block of each axis is padded
    "mha_d64_causal": dict(H=2, Kv=2, D=64, Dv=64, kw=dict(causal=True)),
    "mla_dqk_ne_dv": dict(H=2, Kv=2, D=24, Dv=16, kw=dict(causal=True)),
    "mla_checkpointed": dict(H=2, Kv=2, D=24, Dv=16, kw=dict(causal=True),
                             remat=True),
    "window": dict(H=2, Kv=2, D=16, Dv=16, kw=dict(causal=True, window=9)),
    "softcap": dict(H=2, Kv=2, D=16, Dv=16, kw=dict(causal=True, cap=5.0)),
    "gqa": dict(H=4, Kv=2, D=16, Dv=16, kw=dict(causal=True)),
    "not_causal": dict(H=2, Kv=1, D=16, Dv=16, kw=dict(causal=False)),
}


@pytest.mark.parametrize("case", list(GRAD_CASES))
def test_flash_attention_values_and_grads(case):
    """The differentiable single-stream kernel's output and ``jax.vjp``
    cotangents (dq; dk, dv summed over a GQA group) against the naive
    attention in f32, in interpret mode."""
    from repro.models.attention import naive_attention
    c = GRAD_CASES[case]
    B, S, kw = 1, 40, c["kw"]
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (B, S, c["H"], c["D"]))
    k = jax.random.normal(ks[1], (B, S, c["Kv"], c["D"]))
    v = jax.random.normal(ks[2], (B, S, c["Kv"], c["Dv"]))
    g = jax.random.normal(ks[3], (B, S, c["H"], c["Dv"]))

    def flash(q, k, v):
        return FA.flash_attention(q, k, v, bq=16, bk=16, interpret=True,
                                  **kw)

    def naive(q, k, v):
        return naive_attention(q, k, v, causal=kw["causal"],
                               window=kw.get("window", 0),
                               cap=kw.get("cap"))

    o, vjp = jax.vjp(jax.checkpoint(flash) if c.get("remat") else flash,
                     q, k, v)
    ro, rvjp = jax.vjp(naive, q, k, v)
    for got, want in zip((o,) + vjp(g), (ro,) + rvjp(g)):
        assert got.shape == want.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_mu0_score_probe_degenerates_to_clean():
    qa, qb, k, v, _, _ = _qkv()
    _, ob = FA.zo_dual_flash_attention(
        qa, qb, k, v, seed=7, mu_a=0.0, mu_b=0.0, perturb_b=True,
        bq=16, bk=16, interpret=True)
    fb = FA.flash_attention(qb, k, v, bq=16, bk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(ob), np.asarray(fb),
                               rtol=1e-6, atol=1e-6)


def test_fused_block_size_invariance():
    """The noise the perturbed stream consumes is a pure function of
    (seed, global coords): kernel tiling must not leak into it.  The
    outputs agree across tilings to online-softmax accumulation-order
    rounding (the draws themselves are bit-invariant — see
    test_score_field_tile_windows_bit_identical)."""
    qa, qb, k, v, _, _ = _qkv()
    outs = [FA.zo_dual_flash_attention(qa, qb, k, v, seed=7, mu_b=0.1,
                                       perturb_b=True, bq=bq, bk=bk,
                                       interpret=True)
            for bq, bk in ((8, 8), (16, 16), (32, 16), (16, 32))]
    for oa, ob in outs[1:]:
        np.testing.assert_allclose(np.asarray(ob), np.asarray(outs[0][1]),
                                   rtol=5e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(oa), np.asarray(outs[0][0]),
                                   rtol=5e-4, atol=1e-5)


def test_xla_emulation_matches_interpret():
    """forward_impl="kernel" off-TPU resolves to the jnp emulation; it
    must consume the identical score field."""
    qa, qb, k, v, _, _ = _qkv()
    for kw in (dict(causal=True), dict(causal=True, window=8, cap=5.0)):
        oi = O.zo_dual_flash_attention(qa, qb, k, v, seed=7, mu_b=0.1,
                                       perturb_b=True, impl="interpret",
                                       bq=16, bk=16, **kw)
        ox = O.zo_dual_flash_attention(qa, qb, k, v, seed=7, mu_b=0.1,
                                       perturb_b=True, impl="xla", **kw)
        for a, b in zip(oi, ox):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5)


def test_score_field_tile_windows_bit_identical():
    """The kernel's per-tile noise draws are windows of one global
    (H*Sq, Skv) field: uniform_noise at the kernel's (row_offset,
    col_offset) addressing must equal slices of attn_score_field —
    that is what makes the stream tiling- and batch-invariant."""
    H, Sq, Skv, bq, bk = 3, 32, 48, 16, 16
    field = O.attn_score_field(23, H, Sq, Skv)
    assert field.shape == (H, Sq, Skv)
    for h in range(H):
        for qi in range(Sq // bq):
            for ki in range(Skv // bk):
                tile = uniform_noise(23, (bq, bk),
                                     row_offset=h * Sq + qi * bq,
                                     col_offset=ki * bk)
                np.testing.assert_array_equal(
                    np.asarray(tile),
                    np.asarray(field[h, qi * bq:(qi + 1) * bq,
                                     ki * bk:(ki + 1) * bk]))
    # the Pallas noise materializer is the compiled-path proxy: same
    # stream at the flat (H*Sq, Skv) coordinates
    flat = O.zo_noise(jnp.zeros((H * Sq, Skv)), 23)
    np.testing.assert_array_equal(np.asarray(flat),
                                  np.asarray(field.reshape(H * Sq, Skv)))
    # rep offsets address disjoint row bands of the same stream
    rep1 = uniform_noise(23, (H * Sq, Skv), row_offset=H * Sq)
    assert not np.array_equal(np.asarray(rep1),
                              np.asarray(field.reshape(H * Sq, Skv)))


def test_attn_kv_seed_pred_excludes_kv_projections():
    assert O.attn_kv_seed_pred("layers/attn/wq/w")
    assert O.attn_kv_seed_pred("layers/mlp/fc/w")
    assert not O.attn_kv_seed_pred("layers/attn/wk/w")
    assert not O.attn_kv_seed_pred("layers/attn/wv/w")


def test_attn_score_seed_derivation():
    seeds = {"wq": {"w": jnp.int32(101)}, "wo": {"w": jnp.int32(55)}}
    s = O.attn_score_seed(seeds)
    assert s is not None
    assert int(s) == int(O.fold_seed(jnp.int32(101), O.ATTN_SCORE_SALT))
    assert O.attn_score_seed({"wo": {"w": None}}) is None
    assert O.attn_score_seed({"wq": {"w": None}}) is None


# --- layer / protocol level --------------------------------------------------


def _cfg(probe, impl="kernel_interpret"):
    from repro.configs.gpt2 import gpt2_tiny
    return dataclasses.replace(gpt2_tiny(), forward_impl=impl,
                               attn_probe=probe)


def test_scores_mode_clean_half_matches_plain_forward():
    """With attn_probe="scores" the K/V projections run once on the
    clean half and are shared; the clean stream must still match the
    plain forward, the perturbed stream must stay finite and move."""
    from repro.distributed.sharding import AxisRules
    from repro.models import transformer as T
    cfg = _cfg("scores")
    rules = AxisRules(mesh=None)
    client = T.init_lm(jax.random.PRNGKey(0), cfg)["client"]
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0,
                              cfg.vocab)
    seeds = O.leaf_seed_tree(client, jnp.int32(13), O.attn_kv_seed_pred)
    flat, _ = jax.tree.flatten(seeds, is_leaf=lambda x: x is None)
    assert any(l is None for l in flat)      # wk/wv left the seed stream
    assert any(l is not None for l in flat)
    pz = O.Perturb(seeds=seeds, mu=0.01, dual=True, impl="interpret")
    s2, _ = T.client_forward(client, cfg, rules, toks, None, perturb=pz)
    s_plain, _ = T.client_forward(client, cfg, rules, toks, None)
    B = toks.shape[0]
    np.testing.assert_allclose(np.asarray(s2[:B]), np.asarray(s_plain),
                               rtol=2e-5, atol=1e-5)
    pert = np.asarray(s2[B:])
    assert np.isfinite(pert).all()
    assert np.abs(pert - np.asarray(s_plain)).max() > 1e-4
    # mu=0: the score probe and the weight probe both vanish
    pz0 = O.Perturb(seeds=seeds, mu=0.0, dual=True, impl="interpret")
    s0, _ = T.client_forward(client, cfg, rules, toks, None, perturb=pz0)
    np.testing.assert_allclose(np.asarray(s0[B:]), np.asarray(s_plain),
                               rtol=2e-5, atol=1e-5)


def test_scores_mode_fed_round_lean_matches_dense_h1():
    """End to end at the paper's contract: with the score-level probe
    the lean (seed, coeff) uplink still reconstructs the dense
    aggregate bit-for-bit up to FMA rounding — the phantom score
    direction cancels out of the replay because wk/wv are excluded
    from the seed stream on BOTH the client and the server."""
    from repro.core import protocols as P
    from repro.core import zo as Z
    from repro.data.pipeline import round_batches
    from repro.data.synthetic import BigramLM
    from repro.distributed.sharding import AxisRules
    from repro.models import transformer as T
    from repro.optim.optimizers import make_optimizer
    cfg = _cfg("scores")
    rules = AxisRules(mesh=None)
    api = P.lm_api(cfg, rules)
    assert api.seed_pred is O.attn_kv_seed_pred
    ds = BigramLM(vocab=cfg.vocab, seq_len=17, seed=0)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    sopt = make_optimizer("adamw", 2e-3)
    state = {"client": params["client"], "server": params["server"],
             "opt_server": sopt.init(params["server"])}
    lr = 1e-2
    zo = Z.ZOConfig(mu=1e-3, n_pairs=1)
    fed = P.FedConfig(n_clients=2, h=1)
    rb = round_batches(ds, jax.random.PRNGKey(3), 2, 1, 4)
    copt = make_optimizer("zo_sgd", lr)
    dense = jax.jit(P.make_fed_round(api, "heron", zo, fed, copt, sopt))
    lean = jax.jit(P.make_fed_round(api, "heron", zo, fed, copt, sopt,
                                    uplink="seed_replay", client_lr=lr))
    sd, _ = dense(state, rb, jax.random.PRNGKey(9))
    sl, ml = lean(state, rb, jax.random.PRNGKey(9))
    for a, b in zip(jax.tree.leaves(sd["client"]),
                    jax.tree.leaves(sl["client"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)
    # wk/wv never moved: no coeff multiplies a direction on them
    assert float(ml["uplink_bytes"]) < float(ml["uplink_bytes_dense"])
