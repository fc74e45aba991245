"""The gqa/moe block with sparse layers (Keye-VL-2.0-30B-A3B's language
model: a lightning indexer selects each query's keys, GQA over them with
per-head q/k norms, a softmax router with no shared expert) at a small size
on the CPU, against the benchmark's plain reference
(``benchmarks/keye_reference.py``) and against plain causal attention.

Tolerances: both sides compute in float32 (``compute_dtype="float32"``) and
select the same keys; what is left is the order of the additions (the
blocked online softmax against a dense one, grouped matmuls against a loop
over experts, the indexer's loss by blocks against a dense one), a few
float32 ulps a layer: 2e-5 relative on losses and gradient norms, 1e-4 on a
group's gradient as a whole, 1e-3 on three Adam steps (Adam divides by the
root of a squared gradient, which turns 1e-5 of a small entry into 1e-4 of
its step).
"""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import keye_reference as R  # noqa: E402

from pio_tpu.models import seq_layers, seqrec  # noqa: E402
from pio_tpu.models.seqrec import SeqRecConfig, train_seqrec  # noqa: E402
from pio_tpu.parallel import ring  # noqa: E402

V, T, TOPK = 48, 64, 20
CFG = SeqRecConfig(
    attention_kind="gqa", ffn_kind="moe", router_kind="softmax", d_model=32,
    n_layers=2, dense_layers=0, layer_pattern=("sparse",), head_dim=8,
    kv_heads=2, heads_full=4, attn_gate=False, attn_qk_norm=True,
    rope_theta=1e7, norm_eps=1e-6, n_experts=16, experts_first=4,
    experts_held=4, experts_per_token=3, expert_ffn=24, shared_experts=0,
    routed_scale=1.0, index_heads=4, index_head_dim=8, index_topk=TOPK,
    max_len=T, batch_size=2, steps=3, learning_rate=1e-3,
    compute_dtype="float32", stream="off", seed=11,
)
#: the same model as the benchmark's reference reads it
M = dict(
    vocab_size=V, hidden_size=32, num_hidden_layers=2, head_dim=8, heads=4,
    kv_heads=2, rms_norm_eps=1e-6, rope_theta=1e7, index_heads=4,
    index_head_dim=8, index_topk=TOPK, router_width=16, experts_first=4,
    experts_held=4, num_experts_per_tok=3, moe_intermediate_size=24,
    init_std=seq_layers.INIT_STD, embed_init_std=seq_layers.EMBED_INIT_STD,
    learning_rate=1e-3,
)
CONFIG = os.path.join(BENCH, "configs", "keyevl2-30b-ep8.json")


def histories(n=8, seed=0):
    return np.random.default_rng(seed).integers(1, V, (n, T)).astype(np.int32)


def flat(params):
    out = {}
    for group, value in params.items():
        if isinstance(value, dict):
            out.update({f"{group}/{k}": np.asarray(v) for k, v in value.items()})
        else:
            out[group] = np.asarray(value)
    return out


def group_errors(got: dict, want: dict, scale: dict) -> dict:
    """``||got - want|| / ||scale||`` per parameter group of the reference."""
    diff = dict.fromkeys(R.GROUPS, 0.0)
    size = dict.fromkeys(R.GROUPS, 0.0)
    for path, w in want.items():
        g = R.group_of(path, M)
        diff[g] += float(np.sum((np.asarray(got[path], np.float64) - w) ** 2))
        size[g] += float(np.sum(np.asarray(scale[path], np.float64) ** 2))
    return {g: np.sqrt(diff[g] / size[g]) for g in R.GROUPS if size[g] > 0}


def program_loss(params, rows, cfg=CFG):
    """``(loss, aux)`` of one batch as the training step computes it."""
    import jax.numpy as jnp

    rows = jnp.asarray(rows)
    t1 = jnp.pad(rows[:, 1:], ((0, 0), (0, 1)))
    m1 = ((t1 > 0) & (rows > 0)).astype(jnp.float32)
    sums, counters = seqrec._latent_loss_sums(
        params, (rows, t1, m1, t1, m1), cfg, None, None)
    return seqrec._latent_loss(sums, counters, cfg)


@pytest.fixture(scope="module")
def trained():
    """Three Adam steps of the program and of the benchmark's reference."""
    seqs = histories()
    model = train_seqrec(None, seqs, V - 1, CFG)
    ref = R.train(M, seqs, seed=CFG.seed, steps=3, batch=2)
    return seqs, model, ref


def test_the_two_initialisers_agree_to_the_bit():
    ours = flat(seqrec.init_params(V, CFG))
    theirs = R.init_params(M, CFG.seed)
    assert set(ours) == set(theirs)
    for path, value in theirs.items():
        assert np.array_equal(ours[path], np.asarray(value)), path
    # no shared expert, no gate, no dense layer, no router bias
    assert not [p for p in ours if p.split("/")[-1].startswith(("s_", "g_"))]
    assert set(flat(seqrec.param_specs(CFG))) == set(ours)


def test_the_two_groupings_of_the_parameters_agree():
    assert seq_layers.groups_of(CFG) == R.GROUPS
    for path in seq_layers.describe_params(V, CFG):
        assert seq_layers.group_of(path, CFG) == R.group_of(path, M), path
    assert R.group_of("sparse/idx_k_norm_g", M) == "indexer"
    assert R.group_of("sparse/q_norm", M) == "norms"


def _cell_config():
    """The cell's ``SeqRecConfig``, from its file as the driver reads it."""
    sys.path.insert(0, BENCH)
    import run

    from pio_tpu.controller.params import params_from_dict
    from pio_tpu.templates.sequence import SeqRecParams

    with open(CONFIG) as f:
        config = json.load(f)
    driver = run.load_module("drivers", "train_seq_cfg")
    p = params_from_dict(SeqRecParams, driver.algorithm_params(
        config, R.model(config), 1))
    return config, SeqRecConfig(**{
        f.name: getattr(p, f.name) for f in dataclasses.fields(SeqRecConfig)})


def test_the_program_holds_the_files_hand_count_of_parameters():
    """Six layers of 96,899,456 and the two tables' 18,992 rows each: the
    program's ``describe_params`` at the cell's sizes, the reference's shapes
    and the file's own count agree on 659,190,016."""
    config, cfg = _cell_config()
    vocab = config["data"]["n_items"] + 1
    desc = seq_layers.describe_params(vocab, cfg)
    total = sum(int(np.prod(leaf.shape)) for leaf in desc.values())
    m = R.model(config)
    assert total == config["deployment"]["parameters_here"] == 659_190_016
    assert {k: v.shape for k, v in desc.items()} == R.shapes(m)
    layer = sum(int(np.prod(leaf.shape[1:])) for k, leaf in desc.items()
                if k.startswith("sparse/"))
    assert layer == 96_899_456
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk) == (16, 64, 2048)
    assert seq_layers.groups_of(cfg) == R.GROUPS


@pytest.mark.parametrize("key", ["l_main", "l_index", "l_select", "pairs",
                                 "grad_norm"])
def test_the_per_step_trace_matches_the_reference(trained, key):
    _seqs, model, ref = trained
    np.testing.assert_allclose(model.trace[key], ref[key], rtol=2e-5)
    assert model.trace["dropped"].sum() == 0


@pytest.mark.parametrize("group", R.GROUPS)
def test_three_adam_steps_match_the_reference(trained, group):
    _seqs, model, ref = trained
    update = {k: ref["final"][k] - ref["init"][k] for k in ref["final"]}
    assert group_errors(flat(model.params), ref["final"], update)[group] < 1e-3


@pytest.fixture(scope="module")
def gradients():
    """Both losses and the gradients of one batch: the program's and the
    reference's, from the same weights."""
    import jax
    import jax.numpy as jnp

    rows = histories(2, seed=3)
    (loss, aux), grads = jax.value_and_grad(program_loss, has_aux=True)(
        seqrec.init_params(V, CFG), rows)
    (ref_loss, ref_aux), ref_grads = jax.value_and_grad(
        lambda p: R.batch_loss(p, jnp.asarray(rows), M), has_aux=True)(
            R.init_params(M, CFG.seed))
    return ((float(loss), float(aux["l_index"]), flat(grads)),
            (float(ref_loss), float(ref_aux[1]), jax.device_get(ref_grads)))


def test_both_losses_match_the_reference(gradients):
    ours, ref = gradients
    assert ours[0] == pytest.approx(ref[0], rel=2e-5)
    assert ours[1] == pytest.approx(ref[1], rel=2e-5)
    assert ours[1] > 0


@pytest.mark.parametrize("group", R.GROUPS)
def test_the_gradients_match_the_reference(gradients, group):
    ours, ref = gradients
    assert group_errors(ours[2], ref[2], ref[2])[group] < 1e-4


def test_serving_scores_are_the_references_last_logits(trained):
    seqs, model, ref = trained
    scores = model.next_item_scores(seqs[:2])
    for r in range(2):
        np.testing.assert_allclose(
            scores[r], R.next_item_logits(ref["final"], seqs[r], M), atol=2e-5)


# --------------------------------------------------------- the sparse layer
def _layer(cfg=CFG, seed=5, n=T, b=2):
    """One sparse layer's weights (no layer dim) and a residual stream."""
    import jax

    desc = seq_layers._gqa_leaves(1, cfg, "sparse")
    blk = {k: v[0] for k, v in seq_layers.init_from(
        {"b/" + k: leaf for k, leaf in desc.items()}, seed)["b"].items()}
    h = jax.random.normal(jax.random.PRNGKey(seed), (b, n, cfg.d_model))
    return blk, h


@pytest.mark.parametrize("block", [16, 512])
def test_a_topk_past_the_row_is_plain_causal_gqa(block, monkeypatch):
    """With ``index_topk`` at least the row every query keeps every earlier
    key: the sparse layer's output and its gradients in the stream and the
    four projections are a full causal gqa layer's (no gate, the same q/k
    norms and RoPE), whatever the attention's blocks."""
    import jax

    monkeypatch.setattr(seq_layers, "ATTN_BLOCK", block)
    blk, h = _layer()
    sparse = dataclasses.replace(CFG, index_topk=T)
    full = dataclasses.replace(CFG, layer_pattern=("full",))
    w = jax.random.normal(jax.random.PRNGKey(9), h.shape)

    def loss(fn):
        def of(h, blk):
            return (fn(blk, h) * w).sum()
        return jax.value_and_grad(of, (0, 1))(h, blk)

    got = loss(lambda blk, h: seq_layers.dsa(blk, h, sparse)[0])
    want = loss(lambda blk, h: seq_layers.gqa(blk, h, full, None, "full")[0])
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    np.testing.assert_allclose(got[1][0], want[1][0], atol=2e-5)
    for name in ("q_proj", "k_proj", "v_proj", "o_proj", "q_norm", "k_norm"):
        np.testing.assert_allclose(got[1][1][name], want[1][1][name],
                                   atol=2e-5, err_msg=name)
    # the indexer takes no gradient from the layer's output
    assert not any(np.abs(np.asarray(got[1][1][n])).max()
                   for n in blk if n.startswith("idx_"))


def _oracle(scores, k):
    """The selection by a stable sort of each row, largest first: the first
    ``k`` seen keys in that order (+0.0 and -0.0 one score)."""
    scores = np.where(scores == 0, 0.0, scores)
    sel = np.zeros(scores.shape, bool)
    ties = np.zeros(scores.shape[:-1], bool)
    for idx in np.ndindex(scores.shape[:-1]):
        row = scores[idx]
        seen = np.flatnonzero(row > -np.inf)
        order = seen[np.argsort(-row[seen], kind="stable")]
        sel[idx][order[:k]] = True
        ties[idx] = len(order) > k and row[order[k - 1]] == row[order[k]]
    return sel, ties


@pytest.mark.parametrize("k", [10, 3])
def test_the_selection_is_a_stable_sort_with_ties_to_the_earlier_key(k):
    """Rows with planted ties (few distinct values, +0.0 and -0.0 among them,
    a row with fewer seen keys than ``k``, one seeing exactly ``k``): the
    bisection takes each row's ``k`` largest, the earlier key of a tie
    first, and counts the rows tied at the boundary."""
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    n, t = 24, 96
    scores = rng.integers(-3, 4, (2, n, t)).astype(np.float32) * 0.5
    scores[0, 3] = -scores[0, 3] * 0.0  # -0.0 and +0.0 only
    scores[1, 5] = rng.standard_normal(t).astype(np.float32)  # no tie
    pos = np.arange(n)[:, None] * 4 + np.array([0, 3])[:, None, None]
    scores = np.where(np.arange(t)[None, None, :] <= pos, scores, -np.inf)
    got, ties = seq_layers.top_keys(jnp.asarray(scores), k)
    want, want_ties = _oracle(scores, k)
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(np.asarray(ties), want_ties)
    assert want_ties.sum() > 10 and (want.sum(-1) < 10).any()


def test_the_indexer_learns_from_its_own_loss_alone(monkeypatch):
    """Under the LM loss alone the indexer's gradient is zero, and the main
    model's gradients do not move when the indexer loss's weight does."""
    import jax

    rows = histories(2, seed=4)
    params = seqrec.init_params(V, CFG)

    def grads(weight):
        monkeypatch.setattr(seqrec, "INDEX_LOSS_WEIGHT", weight)
        return flat(jax.grad(lambda p: program_loss(p, rows)[0])(params))

    alone, one, five = grads(0.0), grads(1.0), grads(5.0)
    for path, g in alone.items():
        if seq_layers.group_of(path, CFG) == "indexer":
            assert not np.abs(g).max(), path
            assert np.abs(one[path]).max() > 0, path
            np.testing.assert_allclose(five[path], 5 * one[path], rtol=1e-4,
                                       atol=1e-9)
        else:
            np.testing.assert_array_equal(one[path], alone[path], err_msg=path)
            np.testing.assert_array_equal(five[path], alone[path], err_msg=path)


def _plain_index_loss(ip, xi, q, k, lse, sel, cfg, scale):
    """The indexer's loss as one dense expression, for plain autodiff: the
    einsum form of the scores over every key, float32, no custom VJP."""
    import jax
    import jax.numpy as jnp

    qi, ki, w = seq_layers.indexer(ip, xi, cfg)
    B, n, H, d = q.shape
    s = jnp.einsum("bqhd,bkd->bqhk", qi, ki)
    scores = (jax.nn.relu(s) * w[..., None]).sum(axis=2) * ki.shape[-1] ** -0.5
    kg = jnp.repeat(k, H // k.shape[2], axis=2)
    sa = jnp.einsum("bqhd,bkhd->bqhk", q, kg) * scale
    p = jnp.where(sel, jnp.exp(sa - lse[..., None]).sum(axis=2) / H, 0.0)
    z = jax.nn.logsumexp(jnp.where(sel, scores, -jnp.inf), axis=-1)
    plogp = jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0)
    return (plogp.sum(-1) - jnp.where(sel, p * scores, 0.0).sum(-1)
            + z * p.sum(-1)).sum()


@pytest.fixture(scope="module")
def one_walk():
    """``at(cotangent)`` -> a layer's indexer loss and its gradient in the
    indexer's parameters at that cotangent, the program's (walked once) and
    plain autodiff's, and the selected pairs: two query blocks of 32, each
    walked 16 queries at a time; one compiled program for every
    cotangent."""
    import jax
    import jax.numpy as jnp

    cfg, blk_size = CFG, 32
    blk, h = _layer(cfg, seed=8)
    B, H, Hkv, d = 2, cfg.heads_full, cfg.kv_heads, cfg.head_dim
    q = jax.random.normal(jax.random.PRNGKey(1), (B, T, H, d))
    k = jax.random.normal(jax.random.PRNGKey(2), (B, T, Hkv, d))
    scale = d ** -0.5

    @jax.jit
    def at(cotangent):
        xi = seq_layers.rms_norm(h, blk["attn_norm"], cfg.norm_eps)
        ip = {name: blk[name] for name in seq_layers.INDEXER}
        select = seq_layers.select_keys(*seq_layers.indexer(ip, xi, cfg),
                                        TOPK, blk_size, blk_size,
                                        jnp.float32)[0]
        words = ring.select_words(blk_size)
        sel = jnp.concatenate([ring.unpack_selection(
            select[0][:, i * words:(i + 1) * words], blk_size)
            for i in range(T // blk_size)], axis=1)
        sa = jnp.einsum("bqhd,bkhd->bqhk", q, jnp.repeat(k, H // Hkv, axis=2))
        lse = jax.nn.logsumexp(
            jnp.where(sel[:, :, None], sa * scale, -jnp.inf), axis=-1)
        got, back = jax.vjp(lambda ip: seq_layers.index_loss(
            ip, xi, q, k, lse, select, blk_size, blk_size, scale, cfg), ip)
        want, back_plain = jax.vjp(lambda ip: _plain_index_loss(
            ip, xi, q, k, lse, sel, cfg, scale), ip)
        return (got, want, back(cotangent)[0], back_plain(cotangent)[0],
                sel.sum())

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(seq_layers, "INDEX_BLOCK", 16)
        at(jnp.float32(1.0))  # traced with the walk's blocks of 16
    return at


@pytest.mark.parametrize("cotangent", [1.0, 2.0 ** -14, 0.3])
def test_the_one_walk_loss_gives_plain_autodiffs_value_and_gradient(
        one_walk, cotangent):
    """The indexer's loss of a layer, walked once (its gradient in the
    indexer's parameters taken in the forward pass and scaled in the
    backward), against plain autodiff of the same KL: the same value and
    the same gradient of every indexer parameter, whatever the loss's
    cotangent (2 ** -14 is the cell's), within float32's summation order."""
    import jax.numpy as jnp

    got, want, grads, plain, pairs = one_walk(jnp.float32(cotangent))
    assert 0 < int(pairs) < 2 * T * (T + 1) // 2  # a real selection
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    assert float(got) > 0
    for name in seq_layers.INDEXER:
        bound = float(np.abs(plain[name]).max())
        assert bound > 0, name
        np.testing.assert_allclose(grads[name], plain[name], rtol=1e-4,
                                   atol=1e-5 * bound, err_msg=name)


def _kernels_and_targets(fn, *args):
    """``Counter`` of the Pallas calls (by kernel name) and of the indexer
    target's key-block loops (``while`` under ``seq.dsa/kl``) in the jaxpr
    of ``fn(*args)``, every nested jaxpr walked."""
    import collections

    import jax

    found = collections.Counter()

    def walk(jaxpr, scope):
        for eqn in jaxpr.eqns:
            here = f"{scope}/{eqn.source_info.name_stack}"
            if eqn.primitive.name == "pallas_call":
                found[eqn.params["name"]] += 1
            if eqn.primitive.name == "while" and "seq.dsa/kl" in here:
                found["target"] += 1
            for value in eqn.params.values():
                for sub in value if isinstance(value, (tuple, list)) else [value]:
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner, here)

    walk(jax.make_jaxpr(fn)(*args).jaxpr, "")
    return found


def test_a_layers_gradient_walks_the_indexers_queries_once(monkeypatch):
    """The step's loss through the sparse layers, the indexer on its kernels
    (interpreted): differentiated, a layer runs the index scores' forward
    kernel twice (the selection and the loss's one walk), its backward
    kernel once and the target once, the layer's recomputation running
    neither (what its checkpoint keeps: the selection and the indexer's
    gradient); undifferentiated, the same forward work and no backward
    kernel. The layers are scanned: the jaxpr holds one layer."""
    import jax

    monkeypatch.setattr(seq_layers, "index_impl",
                        lambda *a: "pallas_interpret")
    rows = np.random.default_rng(3).integers(1, V, (2, 128)).astype(np.int32)
    params = seqrec.init_params(V, WIDE_INDEX)
    loss = lambda p: program_loss(p, rows, WIDE_INDEX)
    trained = _kernels_and_targets(
        jax.value_and_grad(lambda p: loss(p)[0]), params)
    assert trained == {"index_scores_fwd": 2, "index_scores_bwd": 1,
                       "target": 1}
    assert _kernels_and_targets(loss, params) == {"index_scores_fwd": 2,
                                                  "target": 1}


def test_selected_pairs_count_the_selection(trained):
    """``selected_pairs`` is summed from the selection itself: rows x layers
    x ``sum_t min(t + 1, k)`` a step; the attention's key-block counters are
    the loops' own bounds (one block a row here)."""
    _seqs, model, _ref = trained
    per_row = sum(min(t + 1, TOPK) for t in range(T))
    np.testing.assert_array_equal(model.trace["selected_pairs"],
                                  [2 * CFG.n_layers * per_row] * 3)
    np.testing.assert_array_equal(model.trace["causal_key_blocks"],
                                  [CFG.n_layers] * 3)
    assert model.trace["topk_boundary_ties"].shape == (3,)


def test_the_expert_shares_add_up_to_the_uncut_layer():
    """The share test: the eight shares of two experts each, the
    router counted once and no shared expert, are the uncut 16-expert
    layer's result, the program's and the reference's alike."""
    import jax

    cfg = dataclasses.replace(CFG, experts_first=0, experts_held=16)
    desc = seq_layers._moe_leaves(1, cfg)
    assert not [n for n in desc if n.startswith("s_")]
    blk = {k: v[0] for k, v in seq_layers.init_from(
        {"b/" + k: leaf for k, leaf in desc.items()}, 5)["b"].items()}
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 48, cfg.d_model))
    m = dict(M, experts_first=0, experts_held=16)
    want, _load, ref_pairs = R._moe(blk, x[0], m, None, None)
    whole, counters = seq_layers.moe(blk, x, cfg, None)
    total, pairs = 0.0, 0.0
    for first in range(0, 16, 2):
        part = dict(blk, **{n: blk[n][first:first + 2]
                            for n in ("e_gate", "e_up", "e_down")})
        y, c = seq_layers.moe(part, x, dataclasses.replace(
            cfg, experts_first=first, experts_held=2), None)
        total, pairs = total + y, pairs + float(c["pairs"])
        np.testing.assert_array_equal(c["load"], counters["load"])
    np.testing.assert_allclose(total[0], want, atol=2e-6)
    np.testing.assert_allclose(whole[0], want, atol=2e-6)
    assert pairs == float(ref_pairs) == x.shape[1] * cfg.experts_per_token


# --------------------------------------------- the tiles under a selection
KB = 128


def _selection(t, bq, density, seed=0, empty=()):
    """A random causal selection (the diagonal always in) as
    ``attention_partial`` takes it, with the key blocks ``empty`` ``(i,
    j)`` left without a selected key."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    sel = (rng.random((1, t, t)) < density) & np.tril(np.ones((t, t), bool))
    sel[:, np.arange(t), np.arange(t)] = True
    for i, j in empty:
        sel[:, i * bq:(i + 1) * bq, j * bq:(j + 1) * bq] = False
    nq = t // bq
    bits = jnp.concatenate([ring.pack_selection(jnp.asarray(
        sel[:, i * bq:(i + 1) * bq])) for i in range(nq)], axis=1)
    active = jnp.asarray(sel.reshape(1, nq, bq, nq, bq).any(axis=(0, 2, 4)))
    return sel, (bits,) + ring.selected_blocks(active)


def test_the_packed_selection_unpacks_to_itself():
    sel, (bits, order, count) = _selection(256, 64, 0.2, empty=[(3, 1)])
    w = ring.select_words(64)
    for i in range(4):
        rows = bits[:, i * w:(i + 1) * w]
        np.testing.assert_array_equal(
            ring.unpack_selection(rows, 64), sel[:, i * 64:(i + 1) * 64])
        np.testing.assert_array_equal(
            ring.unpack_selection(rows, 64, 16, 32),
            sel[:, i * 64 + 16:i * 64 + 48])
    assert [int(c) for c in count] == [1, 2, 3, 3]
    assert [int(j) for j in order[3, :3]] == [0, 2, 3]


@pytest.mark.parametrize("group", [1, 3])
def test_xla_and_pallas_tiles_agree_under_a_selection(group):
    """Forward ``o``, ``lse``, the tile counts and the three gradients of the
    selected attention, XLA's loops against the kernels in interpret mode,
    and both against a dense softmax over the same mask."""
    import jax
    import jax.numpy as jnp

    t, d, hkv = 4 * KB, 128, 2
    sel, select = _selection(t, KB, 0.1, empty=[(3, 1), (2, 0)])
    keys = jax.random.split(jax.random.PRNGKey(group), 4)
    q = jax.random.normal(keys[0], (1, t, hkv * group, d)).astype(jnp.bfloat16)
    k, v = (jax.random.normal(kk, (1, t, hkv, d)).astype(jnp.bfloat16)
            for kk in keys[1:3])
    w = jax.random.normal(keys[3], (1, hkv, t * group, d))
    qh = ring.fold_groups(q, KB, group)
    kh, vh = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)

    def run(impl):
        def loss(qh, kh, vh):
            o, lse, tiles = ring._attention(
                qh, kh, vh, jnp.int32(0), jnp.int32(0), True, d ** -0.5, KB,
                KB, 0, group, impl, select)
            return (o * w).sum(), (o, lse, tiles)
        return jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(qh, kh, vh)

    (_, (o, lse, tiles)), grads = run("pallas_interpret")
    (_, (o_x, lse_x, tiles_x)), grads_x = run("xla")
    assert [int(a) for a in tiles] == [int(a) for a in tiles_x] == [8, 10]
    np.testing.assert_allclose(o, o_x, atol=2e-4)
    np.testing.assert_allclose(lse, lse_x, atol=5e-6)
    for g, g_x in zip(grads, grads_x):
        scale = float(jnp.abs(g_x.astype(jnp.float32)).max())
        np.testing.assert_allclose(g.astype(jnp.float32),
                                   g_x.astype(jnp.float32),
                                   atol=2.0 ** -7 * max(scale, 1.0))
    qf, kf, vf = (a.astype(jnp.float32) for a in (q, k, v))
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qf.reshape(1, t, hkv, group, d),
                   kf) * d ** -0.5
    p = jax.nn.softmax(jnp.where(jnp.asarray(sel)[:, None, None], s, -jnp.inf))
    dense = jnp.einsum("bhgqk,bkhd->bqhgd", p, vf).reshape(1, t, hkv * group, d)
    np.testing.assert_allclose(ring.unfold_groups(o_x, KB, group), dense,
                               atol=5e-3)


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_key_blocks_no_query_selected_are_skipped_not_masked(impl):
    """The keys of a block no query of a query block selected are poisoned:
    a masked tile would multiply 0 by NaN; a skipped one never reads them,
    forward or backward."""
    import jax
    import jax.numpy as jnp

    t, d = 3 * KB, 128
    _sel, select = _selection(t, KB, 0.1, seed=2, empty=[(2, 1)])
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    q, k, v = (jax.random.normal(kk, (1, 1, t, d)).astype(jnp.bfloat16)
               for kk in keys[:3])
    w = jax.random.normal(keys[3], (1, 1, t, d))
    bad_k = k.at[:, :, KB:2 * KB].set(jnp.nan)
    bad_v = v.at[:, :, KB:2 * KB].set(jnp.nan)

    def last_block(q, k, v):
        o = ring._attention(q, k, v, jnp.int32(0), jnp.int32(0), True,
                            d ** -0.5, KB, KB, 0, 1, impl, select)[0]
        return (o[:, :, 2 * KB:] * w[:, :, 2 * KB:]).sum()

    got = jax.value_and_grad(last_block)(q, bad_k, bad_v)
    want = jax.value_and_grad(last_block)(q, k, v)
    assert np.isfinite(float(got[0]))
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-6)
    np.testing.assert_allclose(got[1][:, :, 2 * KB:].astype(jnp.float32),
                               want[1][:, :, 2 * KB:].astype(jnp.float32))


# ------------------------------------------------------ the block's rules
@pytest.mark.parametrize("change,match", [
    (dict(layer_pattern=("sparse", "full")), "sparse layers alone"),
    (dict(index_head_dim=6), "multiple of 4"),
    (dict(index_topk=0), "index_topk"),
    (dict(yarn_factor=4.0), "no YaRN"),
])
def test_a_sparse_block_that_cannot_be_built_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        seq_layers.check_block(dataclasses.replace(CFG, **change))


def test_a_seq_axis_is_refused():
    with pytest.raises(ValueError, match="seq axis of 2"):
        seq_layers.check_block(CFG, n_seq=2)


def test_the_attention_rule_answers_pallas_for_the_cells_sparse_layers():
    _config, cfg = _cell_config()
    assert seq_layers.attn_impls("tpu", cfg, 16384) == {"sparse": "pallas",
                                                        "index": "pallas"}
    assert seq_layers.attn_impls("cpu", cfg, 16384) == {"sparse": "xla"}
    # a block of 128 queries holds 4 word rows of the mask: no whole tile
    assert ring.attention_impl("tpu", "bfloat16", 128, 128, 128, 128, True,
                               16384, True) == "xla"
    assert ring.attention_impl("tpu", "bfloat16", 128, 128, 512, 512, True,
                               16384, True) == "pallas"


def test_the_index_rule_answers_pallas_for_the_cells_indexer_alone():
    """``index_impl`` at the shapes ``select_keys`` hands it: a block of 128
    queries of 16 heads of 64 against 16,384 keys, bfloat16, takes the
    kernels on a TPU; the CPU, float32 operands and a key count no tile
    divides keep the einsum, and so does the map of what ran, which names
    the indexer only where its kernels run (no other block has an entry)."""
    _config, cfg = _cell_config()
    assert (cfg.index_heads, cfg.index_head_dim, cfg.compute_dtype) == (
        16, 64, "bfloat16")
    n = ring.pick_block(ring.pick_block(16384, seq_layers.ATTN_BLOCK),
                        seq_layers.INDEX_BLOCK)
    assert n == 128
    rule = seq_layers.index_impl
    assert rule("tpu", "bfloat16", 16, 64, n, 16384) == "pallas"
    assert rule("cpu", "bfloat16", 16, 64, n, 16384) == "xla"
    assert rule("tpu", "float32", 16, 64, n, 16384) == "xla"
    assert rule("tpu", "bfloat16", 16, 64, n, 16384 + 128) == "xla"
    f32 = dataclasses.replace(cfg, compute_dtype="float32")
    assert seq_layers.attn_impls("tpu", f32, 16384) == {"sparse": "xla"}
    assert seq_layers.attn_impls("tpu", cfg, 16384 + 128) == {"sparse": "xla"}
    dense = dataclasses.replace(cfg, layer_pattern=("full",))
    assert seq_layers.attn_impls("tpu", dense, 16384) == {"full": "pallas"}


#: the layer at widths the indexer's kernels take: a block of 128 queries
#: (the row) of 4 heads of 16 against 128 keys
WIDE_INDEX = dataclasses.replace(CFG, max_len=128, index_head_dim=16)


def test_the_indexers_kernels_give_the_xla_forms_losses_and_gradients(
        monkeypatch):
    """One batch through the step's loss with the indexer's scores on the
    kernels (interpreted) and on the einsum: the same selection (its
    checksum), both losses and every gradient, the indexer's own among
    them, within float32's summation order."""
    import jax

    rows = np.random.default_rng(6).integers(1, V, (2, 128)).astype(np.int32)
    params = seqrec.init_params(V, WIDE_INDEX)

    def run(impl):
        monkeypatch.setattr(seq_layers, "index_impl", lambda *a: impl)
        (loss, aux), grads = jax.value_and_grad(
            lambda p: program_loss(p, rows, WIDE_INDEX), has_aux=True)(params)
        return float(loss), aux, flat(grads)

    loss, aux, grads = run("pallas_interpret")
    loss_x, aux_x, grads_x = run("xla")
    assert float(aux["l_select"]) == float(aux_x["l_select"])
    assert loss == pytest.approx(loss_x, rel=2e-6)
    assert float(aux["l_index"]) == pytest.approx(float(aux_x["l_index"]),
                                                  rel=2e-5)
    assert any(np.abs(g).max() > 0 for k, g in grads.items() if "idx_" in k)
    for name, g in grads.items():
        scale = max(float(np.abs(grads_x[name]).max()), 1e-12)
        np.testing.assert_allclose(g, grads_x[name], atol=2e-5 * scale,
                                   err_msg=name)


def test_the_counters_and_the_indexer_loss_reach_the_stats(monkeypatch):
    """A ``stats`` call reports the four counters (``/train.json`` too) and
    what ran the sparse layers' tiles."""
    from pio_tpu.obs import trainwatch

    seen = {}
    monkeypatch.setattr(trainwatch, "set_counters", seen.update)
    stats = {}
    model = train_seqrec(None, histories(2), V - 1,
                         dataclasses.replace(CFG, steps=1), stats=stats)
    for name in seqrec.DSA_COUNTERS:
        assert stats["counters"][name] == seen[name] == float(
            model.trace[name].sum())
    assert stats["attn_impl"] == {"sparse": "xla"}
    assert model.trace["l_index"].shape == (1,)
    # where the indexer's kernels run, the map names them
    monkeypatch.setattr(seq_layers, "index_impl",
                        lambda *a: "pallas_interpret")
    stats = {}
    rows = np.random.default_rng(2).integers(1, V, (2, 128)).astype(np.int32)
    train_seqrec(None, rows, V - 1,
                 dataclasses.replace(WIDE_INDEX, steps=1), stats=stats)
    assert stats["attn_impl"] == {"sparse": "xla", "index": "pallas_interpret"}


def test_the_selection_checksum_sums_the_selected_positions():
    """``l_select`` is summed from the selection itself: with ``index_topk``
    past the row every query keeps every earlier key, so a step reads rows x
    layers x ``sum_t t (t + 1) / 2``, the program's and the reference's."""
    import jax.numpy as jnp

    rows = histories(2, seed=5)
    whole = dataclasses.replace(CFG, index_topk=T)
    _loss, aux = program_loss(seqrec.init_params(V, whole), rows, whole)
    _loss, ref_aux = R.batch_loss(R.init_params(M, CFG.seed),
                                  jnp.asarray(rows), dict(M, index_topk=T))
    want = 2 * CFG.n_layers * sum(t * (t + 1) // 2 for t in range(T))
    assert float(aux["l_select"]) == float(ref_aux[4]) == want


def test_ties_to_the_later_key_raise_the_checksum():
    """Rows with planted ties: the reference's selection under its
    ``ties_to_later`` fault keeps later keys than the program's, so the
    selected positions' sum rises; without the fault the two agree."""
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    n, t, k = 16, 64, 10
    scores = rng.integers(-2, 3, (n, t)).astype(np.float32)
    scores = np.where(np.arange(t)[None, :] <= np.arange(n)[:, None] * 4 + 3,
                      scores, -np.inf)
    pos = np.arange(t)
    sel, _ties = seq_layers.top_keys(jnp.asarray(scores[None]), k)
    ours = (np.asarray(sel[0]) * pos).sum()
    assert ours == (np.asarray(R._select(jnp.asarray(scores), k, None))
                    * pos).sum()
    later = (np.asarray(R._select(jnp.asarray(scores), k, "ties_to_later"))
             * pos).sum()
    assert later > ours
