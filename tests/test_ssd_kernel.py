"""The chunked scan's Pallas kernels (``pio_tpu.models.ssd_kernel``) in
interpret mode on the CPU, at the two mamba cells' block shapes (64 heads of
64 channels, a state of 128; eight groups of 8 heads with chunks of 128, and
one group of 64 heads with chunks of 256) over three chunks, so that a state
is carried: forward, the five gradients and the counters against XLA's
``seq_layers.ssd_scan`` and the time-step recurrence of the benchmark's
reference; the mixer through either path; ``ssd_impl``'s choice; and where
the choice is reported (``stats``, ``/train.json``, the run record).

Both sides keep matmul operands in the compute dtype and every sum float32,
so under float32 they differ by the order of the additions; under bfloat16
also by where a cotangent is rounded to bfloat16 before a matmul, which moves
a gradient by a few units in bfloat16's last place."""

import dataclasses

import numpy as np
import pytest

from nemotron_small import CFG, R, V, histories

from pio_tpu.models import seq_layers, ssd_kernel
from pio_tpu.models.seqrec import train_seqrec

P, N = 64, 128

CASES = {  # heads, groups, chunk, compute dtype
    "nemotron_bf16": (64, 8, 128, "bfloat16"),
    "nemotron_f32": (64, 8, 128, "float32"),
    "granite_bf16": (64, 1, 256, "bfloat16"),
    "granite_f32": (64, 1, 256, "float32"),
}


def _inputs(h, g, t, seed=0):
    """Step sizes and decays that leave about a third of a state after a
    chunk of 256 steps: what a chunk carries into the next is not lost."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(keys[0], (1, t, h, P)),
            jax.random.uniform(keys[1], (1, t, h), minval=1e-3, maxval=2e-2),
            -jnp.exp(jax.random.uniform(keys[2], (h,), minval=-2.0, maxval=0.0)),
            jax.random.normal(keys[3], (1, t, g, N)) * 0.3,
            jax.random.normal(keys[4], (1, t, g, N)) * 0.3,
            jax.random.normal(keys[5], (h,)),
            jnp.cos(jnp.arange(t * h * P, dtype=jnp.float32)).reshape(1, t, h, P))


def _kernels(x, dt, a, b, c, d, q, cd):
    """``ssd_kernel.scan`` on ``x``, ``B``, ``C`` laid side by side as the
    convolution leaves them; ``y`` as ``[B, T, H, P]``."""
    import jax.numpy as jnp

    bt, t, h, p = x.shape
    g, n = b.shape[2:]
    xbc = jnp.concatenate([x.reshape(bt, t, h * p), b.reshape(bt, t, g * n),
                           c.reshape(bt, t, g * n)], axis=-1)
    y, *counts = ssd_kernel.scan(xbc, dt, a, d, (h, p, g, n), q, cd,
                                 interpret=True)
    return (y.reshape(x.shape), *counts)


def _xla(x, dt, a, b, c, d, q, cd):
    y, *counts = seq_layers.ssd_scan(x, dt, a, b, c, q, cd,
                                     seq_layers.SSM_HEAD_BLOCK)
    return (y + d[:, None] * x, *counts)


def _step_by_step(x, dt, a, b, c, d):
    import jax
    import jax.numpy as jnp

    heads = jnp.arange(x.shape[2]) // (x.shape[2] // b.shape[2])
    return jax.vmap(lambda x, dt, b, c: R.recurrence(
        dt[:, :, None] * x, dt, a, b, c, heads))(x, dt, b, c) + d[:, None] * x


def _close(got, want, rel):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    np.testing.assert_allclose(got, want, atol=rel * np.abs(want).max())


@pytest.fixture(scope="module")
def runs():
    """``{case: (inputs, kernel's (out, grads), XLA's (out, grads))}``, each
    side's ``out`` the scan's four returns."""
    import jax
    import jax.numpy as jnp

    done = {}
    for name, (h, g, q, cd) in CASES.items():
        *args, weight = _inputs(h, g, 3 * q)
        cd = jnp.dtype(cd)

        def both(scan, args=args, weight=weight, q=q, cd=cd):
            run = lambda *a: scan(*a, q, cd)
            loss = lambda *a: (run(*a)[0] * weight).sum()
            return (jax.jit(run)(*args),
                    jax.jit(jax.grad(loss, (0, 1, 2, 3, 4, 5)))(*args))

        done[name] = (args + [weight], both(_kernels), both(_xla))
    return done


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernels_are_xlas_scan(runs, case):
    """Forward ``y`` (with ``D x``) and the gradients of ``x``, ``dt``,
    ``a``, ``B``, ``C`` and ``D``."""
    _args, (out, grads), (out_x, grads_x) = runs[case]
    bf16 = CASES[case][3] == "bfloat16"
    _close(out[0], out_x[0], 2e-3 if bf16 else 2e-6)
    for g, g_x in zip(grads, grads_x):
        assert g.shape == g_x.shape and g.dtype == g_x.dtype
        _close(g, g_x, 1e-2 if bf16 else 2e-5)


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernels_counters_are_xlas(runs, case):
    """``ssm_chunks`` (rows x chunks) and ``ssm_state_absmax`` as XLA's scan
    counts them; the head blocks are the kernels' grid's."""
    h, g, q, _cd = CASES[case]
    _args, (out, _), (out_x, _) = runs[case]
    assert float(out[1]) == float(out_x[1]) == 3.0
    assert float(out[2]) == pytest.approx(float(out_x[2]), rel=1e-5)
    assert float(out[2]) > 0.1  # a state was carried
    assert float(out[3]) == h // ssd_kernel.head_block(h // g, P) == 8


@pytest.mark.parametrize("case", ["nemotron_f32", "granite_f32"])
def test_the_kernels_are_the_recurrence(runs, case):
    """Against the reference's time-step recurrence, forward and backward."""
    import jax

    args, (out, grads), _ = runs[case]
    *args, weight = args
    _close(out[0], _step_by_step(*args), 2e-5)
    want = jax.jit(jax.grad(lambda *a: (_step_by_step(*a) * weight).sum(),
                            (0, 1, 2, 3, 4, 5)))(*args)
    for g, w in zip(grads, want):
        _close(g, w, 1e-4)


def test_a_lost_state_is_seen(runs):
    """The kernel carries the state: a fourth chunk alone, from zeros, is
    not the last chunk of the four."""
    import jax.numpy as jnp

    args, (out, _), _ = runs["granite_f32"]
    x, dt, a, b, c, d, _w = args
    q = CASES["granite_f32"][2]
    alone = _kernels(x[:, -q:], dt[:, -q:], a, b[:, -q:], c[:, -q:], d, q,
                     jnp.float32)[0]
    gap = float(jnp.abs(alone - out[0][:, -q:]).max())
    assert gap > 0.05 * float(jnp.abs(out[0]).max())


def test_the_mixer_runs_either_path(monkeypatch):
    """One Mamba-2 mixer at the kernels' widths (16 heads in 2 groups, chunks
    of 128, two rows) under the bfloat16 policy: the kernel path is the XLA
    path's output, gradient and counters."""
    import jax
    import jax.numpy as jnp

    cfg = dataclasses.replace(CFG, ssm_heads=16, ssm_head_dim=P, ssm_groups=2,
                              ssm_state=N, ssm_chunk=128,
                              compute_dtype="bfloat16")
    blk = {k: v[0] for k, v in seq_layers.init_from(
        {"b/" + k: leaf for k, leaf in seq_layers._mamba_leaves(1, cfg).items()},
        5)["b"].items()}
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 256, cfg.d_model))

    def run():
        def loss(blk, h):
            out, counters = seq_layers.mamba(blk, h, cfg)
            return (out * jnp.sin(out)).sum(), (out, counters)

        return jax.jit(jax.grad(loss, (0, 1), has_aux=True))(blk, h)

    want_grads, (want, want_counters) = run()
    monkeypatch.setattr(seq_layers, "ssd_impl", lambda *a: "pallas_interpret")
    grads, (out, counters) = run()
    _close(out, want, 2e-3)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        _close(g, w, 2e-2)
    assert float(counters["ssm_chunks"]) == float(want_counters["ssm_chunks"]) == 4
    assert float(counters["ssm_state_absmax"]) == pytest.approx(
        float(want_counters["ssm_state_absmax"]), rel=1e-5)
    # XLA's map takes a group of 8 heads a turn, the kernels' grid 8 heads
    assert float(counters["ssm_head_blocks"]) == float(
        want_counters["ssm_head_blocks"]) == 2


@pytest.mark.parametrize("platform, cd, chunk, p, n, per_group, want", [
    ("tpu", "bfloat16", 128, 64, 128, 8, "pallas"),    # the Nemotron cell
    ("tpu", "bfloat16", 256, 64, 128, 64, "pallas"),   # the Granite cell
    ("tpu", "bfloat16", 512, 64, 128, 8, "pallas"),
    ("tpu", "bfloat16", 128, 128, 128, 16, "pallas"),
    ("tpu", "float32", 128, 64, 128, 8, "xla"),
    ("cpu", "bfloat16", 128, 64, 128, 8, "xla"),
    ("gpu", "bfloat16", 256, 64, 128, 64, "xla"),
    ("tpu", "bfloat16", 64, 64, 128, 8, "xla"),        # a chunk off the lanes
    ("tpu", "bfloat16", 128, 64, 64, 8, "xla"),        # a state of 64
    ("tpu", "bfloat16", 128, 64, 128, 4, "xla"),       # fewer than 8 heads
    ("tpu", "bfloat16", 128, 96, 128, 8, "xla"),       # heads across tiles
    ("tpu", "bfloat16", 8, 2, 8, 32, "xla"),           # the tests' toy block
    ("tpu", "bfloat16", 1024, 64, 128, 8, "xla"),      # [Q, Q] past VMEM
])
def test_ssd_impl_reads_platform_dtype_and_shapes(platform, cd, chunk, p, n,
                                                 per_group, want):
    assert seq_layers.ssd_impl(platform, cd, chunk, p, n, per_group) == want


def test_ssm_impl_reads_the_blocks_widths_and_the_rows():
    """The two cells' mixers ride the kernels on a TPU; a short row's chunk,
    every CPU run and a block without a mamba layer do not."""
    wide = dataclasses.replace(CFG, ssm_heads=64, ssm_head_dim=64, ssm_groups=8,
                               ssm_state=128, ssm_chunk=128,
                               compute_dtype="bfloat16")
    assert seq_layers.ssm_impl("tpu", wide, 16384) == "pallas"
    assert seq_layers.ssm_impl("tpu", dataclasses.replace(
        wide, ssm_groups=1, ssm_chunk=256), 8192) == "pallas"
    assert seq_layers.ssm_impl("cpu", wide, 16384) == "xla"
    assert seq_layers.ssm_impl("tpu", wide, 96) == "xla"
    assert seq_layers.ssm_impl("tpu", CFG, 16384) == "xla"
    no_mamba = dataclasses.replace(CFG, mixer_pattern=("attn", "moe"), n_layers=2)
    assert seq_layers.ssm_impl("tpu", no_mamba, 16384) == "none"


def test_the_choice_reaches_stats_train_json_and_the_run_record():
    from pio_tpu.obs import trainwatch

    stats = {}
    rec = trainwatch.StepRecorder(run_id="r", engine_id="e")
    trainwatch.activate(rec)
    try:
        train_seqrec(None, histories(8, seed=1), V - 1,
                     dataclasses.replace(CFG, seed=3, steps=1), stats=stats)
        payload, summary = rec.payload(), rec.summary()
    finally:
        trainwatch.deactivate()
    # no chip here: XLA's scan ran
    assert stats["ssm_impl"] == payload["ssmImpl"] == summary["ssm_impl"] == "xla"
    record = trainwatch.run_record(
        run_id="r", engine_id="e", status="COMPLETED", train_seconds=1.0,
        phases={}, params_hash="h", step_summary=summary)
    assert record["ssm_impl"] == "xla"
    assert "ssm_impl" not in trainwatch.run_record(
        run_id="r", engine_id="e", status="COMPLETED", train_seconds=1.0,
        phases={}, params_hash="h", step_summary={"steps": 1})
