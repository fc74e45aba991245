"""The passes of the routed experts (``seq_layers.pass_widths``,
``pass_plan``): how many rows of the sorted (token, expert) pairs each pass
of the grouped matmuls stages, and in which chunks, as a pure function of
what a layer sees at trace time."""

import numpy as np
import pytest

from pio_tpu.models import seq_layers


def _passes_before(n_pairs):
    """A quarter of all pairs to a pass, whatever share is held: the rule
    until PR 36."""
    width = min(n_pairs, max(8, -(-(n_pairs // 4) // 8) * 8))
    return -(-n_pairs // width)


@pytest.mark.parametrize("n_pairs, held, n_experts, first", [
    (65_536, 8, 64, 16_384),     # glm47flash-ep8: 16,384 tokens x 4
    (163_840, 8, 256, 10_240),   # laguna-s21-ep32: 16,384 x 10
    (98_304, 8, 128, 12_288),    # nemotron3nano-ep16: 16,384 x 6
    (65_536, 64, 64, 65_536),    # a chip that holds every expert: one pass
    (96, 4, 16, 48),             # the cells' rehearsal
    (16, 1, 64, 8),              # tiny: a whole sublane at least
    (6, 1, 64, 6),               # fewer pairs than a sublane
    (98_304, 1, 128, 1_536),     # one expert of 128 held
    (12_345, 3, 100, 744),       # nothing divides
])
def test_the_first_pass_is_twice_the_balanced_held_share(
        n_pairs, held, n_experts, first):
    passes = seq_layers.pass_widths(n_pairs, held, n_experts)
    assert passes[0] == (first,)
    assert first == n_pairs or (first >= 8 and first % 8 == 0)
    assert first >= min(n_pairs, 2 * n_pairs * held / n_experts)
    # dropless: the passes cover every pair, less than a sublane over each
    widths = [w for chunks in passes for w in chunks]
    assert n_pairs <= sum(widths) < n_pairs + 8 * len(passes)
    assert all(w > 0 for w in widths)
    # the unrolled program does not grow: one pass more than before at most
    assert len(passes) <= _passes_before(n_pairs) + 1
    # nor what a pass holds in memory: its chunks are alike, and none wider
    # than a pass was before (or than the first)
    assert all(len(set(chunks)) == 1 for chunks in passes)
    assert max(widths) <= max(first, -(-(n_pairs // 4) // 8) * 8)
    if first == n_pairs:
        assert passes == ((n_pairs,),)


def test_the_cells_passes_double_and_the_last_runs_in_quarters():
    assert seq_layers.pass_widths(163_840, 8, 256) == (
        (10_240,), (10_240,), (20_480,), (40_960, 40_960, 40_960))
    assert seq_layers.pass_widths(98_304, 8, 128) == (
        (12_288,), (12_288,), (24_576, 24_576, 24_576))
    assert seq_layers.pass_widths(65_536, 8, 64) == (
        (16_384,), (16_384,), (16_384, 16_384))
    # the unrolled passes stop at MOE_MAX_PASSES whatever is left
    assert seq_layers.pass_widths(98_304, 1, 128) == (
        (1_536,), (1_536,), (3_072,), (6_144,), (21_504,) * 4)


@pytest.mark.parametrize("sizes, offsets", [
    ([3, 0, 9, 4], (0, 8, 16)),              # even passes, as before
    ([3, 0, 9, 4], (0, 8, 16, 32, 64)),      # widening, the last ones empty
    ([30, 1, 0, 17, 2], (0, 8, 16, 32, 56)),  # one group over three passes
    ([0, 0, 0], (0, 8, 24)),
    ([5, 5, 5, 5, 5, 5, 5, 5], (0, 40)),     # one pass of everything
])
def test_the_plan_gives_each_pass_its_rows_of_each_group(sizes, offsets):
    """Against a row-by-row count: group ``e``'s rows are ``[start_e,
    end_e)`` of the sorted pairs; pass ``p`` holds those in its slice."""
    import jax.numpy as jnp

    group_of_row = np.repeat(np.arange(len(sizes)), sizes)
    want = np.zeros((len(offsets) - 1, len(sizes)), np.int64)
    for p, (lo, hi) in enumerate(zip(offsets, offsets[1:])):
        for e in group_of_row[lo:hi]:
            want[p, e] += 1
    got = np.asarray(seq_layers.pass_plan(jnp.asarray(sizes, jnp.int32), offsets))
    np.testing.assert_array_equal(got, want)
    assert got.dtype == np.int32
    if sum(sizes) <= offsets[-1]:
        np.testing.assert_array_equal(got.sum(axis=0), sizes)
