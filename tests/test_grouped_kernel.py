"""The grouped products of the expert layer on the repo's Pallas kernels
(ops/grouped_kernel.py), interpreted on the CPU at small shapes: each
of the three forms against a dense masked product in float32, the tile
rule at the cells' shapes, and the ladder `moe_experts` around them
(docs/kernels.md, "The grouped products of the expert layer")."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.ops import dispatch
from skypilot_tpu.ops import grouped_kernel
from skypilot_tpu.ops import grouped_matmul
from skypilot_tpu.utils import faults

ROWS, TM, GROUPS = 64, 16, 4

# name -> the groups' rows, of 64 in tiles of 16
SIZES = {
    'boundary_inside_a_tile': [10, 20, 18, 16],
    'empty_group_first': [0, 30, 20, 14],
    'empty_groups_in_the_middle': [24, 0, 0, 40],
    'empty_groups_last': [40, 24, 0, 0],
    'rows_of_no_group_at_the_end': [10, 7, 16, 5],
    'all_rows_in_one_group': [0, 64, 0, 0],
    'three_groups_in_one_tile': [3, 4, 5, 30],
    'no_rows_at_all': [0, 0, 0, 0],
}
# (a, b, (tk over a, tn over b)): whole matrices, 7 x 128, tiled
EXTENTS = {
    'whole': (128, 256, (128, 256)),
    'seven_lanes': (896, 128, (896, 128)),
    'tiled': (256, 256, (128, 128)),
}


def _rand(seed, shape):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _one_hot(sizes):
    owner = np.searchsorted(np.cumsum(sizes), np.arange(ROWS), side='right')
    return jnp.asarray(owner[:, None] == np.arange(GROUPS)[None, :],
                       jnp.float32)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision('highest'):
        yield


@pytest.mark.parametrize('extents', EXTENTS)
@pytest.mark.parametrize('case', SIZES)
@pytest.mark.parametrize('form', dispatch.GROUPED_FORMS)
def test_form_matches_the_dense_masked_product(form, case, extents):
    a, b, (ta, tb) = EXTENTS[extents]
    sizes = SIZES[case]
    live = sum(sizes)
    own = _one_hot(sizes)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    x, w, y = _rand(0, (ROWS, a)), _rand(1, (GROUPS, a, b)), \
        _rand(2, (ROWS, b))
    visits = grouped_kernel.group_visits(group_sizes, ROWS, TM,
                                         visit_empty=form == 'over_rows')
    assert int(visits.count[0]) <= visits.group.shape[0]
    if form == 'rows':
        got = grouped_kernel.rows_product(x, w, visits, (TM, ta, tb))
        want = jnp.einsum('mg,ma,gab->mb', own, x, w)
        got, want = got[:live], want[:live]
    elif form == 'rows_t':
        got = grouped_kernel.rows_product(y, w, visits, (TM, tb, ta),
                                          transposed=True)
        want = jnp.einsum('mg,mb,gab->ma', own, y, w)
        got, want = got[:live], want[:live]
    else:
        got = grouped_kernel.over_rows_product(x, y, visits, (TM, ta, tb))
        want = jnp.einsum('mg,ma,mb->gab', own, x, y)
        assert got.shape == (GROUPS, a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize('form', ('rows', 'rows_t'))
def test_bf16_rows_are_what_ragged_dot_gives(form):
    """bf16 in, float32 sums, one rounding: `ragged_dot`'s own result."""
    sizes = jnp.asarray(SIZES['rows_of_no_group_at_the_end'], jnp.int32)
    live = int(sizes.sum())
    x = _rand(0, (ROWS, 128)).astype(jnp.bfloat16)
    w = _rand(1, (GROUPS, 128, 256)).astype(jnp.bfloat16)
    y = _rand(2, (ROWS, 256)).astype(jnp.bfloat16)
    visits = grouped_kernel.group_visits(sizes, ROWS, TM)
    if form == 'rows':
        got = grouped_kernel.rows_product(x, w, visits, (TM, 128, 256))
        want = jax.lax.ragged_dot(x, w, sizes)
    else:
        got = grouped_kernel.rows_product(y, w, visits, (TM, 256, 128),
                                          transposed=True)
        want = jax.lax.ragged_dot(y, jnp.swapaxes(w, 1, 2), sizes)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got[:live], np.float32),
        np.asarray(want[:live], np.float32), rtol=2e-2, atol=2e-2)


def test_visits_walk_each_covered_tile_once_a_group():
    sizes = jnp.asarray([10, 20, 0, 18], jnp.int32)       # 48 of 64 rows
    visits = grouped_kernel.group_visits(sizes, ROWS, TM)
    n = int(visits.count[0])
    assert list(zip(visits.group[:n].tolist(), visits.tile[:n].tolist())) \
        == [(0, 0), (1, 0), (1, 1), (3, 1), (3, 2)]
    assert visits.offsets.tolist() == [0, 10, 30, 30, 48]
    # the tile past the groups' total is no visit: its rows are not read
    assert 3 not in visits.tile[:n].tolist()
    with_empty = grouped_kernel.group_visits(sizes, ROWS, TM, True)
    assert int(with_empty.count[0]) == n + 1
    assert with_empty.group[:n + 1].tolist() == [0, 1, 1, 2, 3, 3]


# The cells' calls: (rows of a chunk, rows of the backward's buffers,
# dim, width, groups) of `sft-swa-moe-16k` and `sft-moe-8k`.
CELLS = {
    'sft-swa-moe-16k': (34816, 139264, 2304, 896, 16),
    'sft-moe-8k': (8704, 69632, 2048, 1536, 8),
}


@pytest.mark.parametrize('form', dispatch.GROUPED_FORMS)
@pytest.mark.parametrize('cell', CELLS)
def test_tile_rule_gives_legal_tiles_within_the_counted_vmem(cell, form):
    chunk, buffer_rows, d, width, groups = CELLS[cell]
    rows = buffer_rows if form == 'over_rows' else chunk
    for a, b in ((d, width), (width, d)):
        tiles = dispatch.grouped_blocks(form, rows, a, b, groups,
                                        jnp.bfloat16)
        assert tiles is not None, (cell, form, a, b)
        tm, tk, tn = tiles
        k, n = (b, a) if form == 'rows_t' else (a, b)
        assert dispatch.block_dim_ok(tm, rows, 16)
        assert dispatch.block_dim_ok(tk, k, dispatch.LANES)
        assert dispatch.block_dim_ok(tn, n, dispatch.LANES)
        assert dispatch.grouped_vmem_bytes(form, tm, tk, tn, 2) <= \
            dispatch.GROUPED_VMEM_BUDGET_BYTES


def test_tile_rule_refuses_what_it_cannot_tile():
    # a width that is no whole number of lanes; rows no tile divides
    assert dispatch.grouped_blocks('rows', 4096, 512, 200, 8,
                                   jnp.bfloat16) is None
    assert dispatch.grouped_blocks('rows', 4100, 512, 256, 8,
                                   jnp.bfloat16) is None


@pytest.mark.parametrize('form', dispatch.GROUPED_FORMS)
def test_tile_rule_halves_a_matrix_too_large_to_stay_resident(form):
    """Mixtral's 4,096 x 14,336 in bf16 is 117 MB: tiled, in whole
    lanes that divide the extents, within the budget."""
    tm, tk, tn = dispatch.grouped_blocks(form, 4096, 4096, 14336, 8,
                                         jnp.bfloat16)
    k, n = (14336, 4096) if form == 'rows_t' else (4096, 14336)
    assert tm == 256 and (tk, tn) != (k, n)
    assert k % tk == 0 and tk % dispatch.LANES == 0
    assert n % tn == 0 and tn % dispatch.LANES == 0
    assert dispatch.grouped_vmem_bytes(form, tm, tk, tn, 2) <= \
        dispatch.GROUPED_VMEM_BUDGET_BYTES


def _chunk(rows=256, d=128, width=256, groups=4):
    x = _rand(0, (rows, d))
    w_gate = _rand(1, (groups, d, width)) * d ** -0.5
    w_up = _rand(2, (groups, d, width)) * d ** -0.5
    w_down = _rand(3, (groups, width, d)) * width ** -0.5
    sizes = jnp.asarray([70, 0, 90, 60], jnp.int32)
    live = (jnp.arange(rows) < sizes.sum())[:, None]
    return x, live, w_gate, w_up, w_down, sizes


def test_off_the_tpu_the_rule_answers_ragged_dot():
    assert dispatch.interpret_mode()
    assert grouped_matmul._resolve_rung(34816, 2304, 896, 16,
                                        jnp.bfloat16) == 'ragged_dot'
    dispatch.reset_for_tests()
    grouped_matmul.expert_ffn(*_chunk())
    assert dispatch.snapshot()['moe_experts'] == 'ragged_dot'
    assert dispatch.grouped_plan_line() == ''


class TestLadder:
    """The layer's three entry points with the rule made to answer
    `pallas` (the kernels run interpreted here)."""

    @pytest.fixture(autouse=True)
    def _offer_pallas(self, monkeypatch):
        monkeypatch.setattr(grouped_matmul, '_resolve_rung',
                            lambda *a: 'pallas')
        dispatch.reset_for_tests()
        yield
        faults.reset()

    def test_pallas_rung_matches_the_floor_forward_and_backward(self):
        x, live, w_gate, w_up, w_down, sizes = _chunk()
        g = _rand(4, x.shape)

        def run():
            out = grouped_matmul.expert_ffn(x, live, w_gate, w_up, w_down,
                                            sizes)
            dx, again, kept = grouped_matmul.expert_ffn_bwd(
                x, live, w_gate, w_up, w_down, sizes, g)
            grads = grouped_matmul.expert_weight_grads(
                x, kept[0], g, kept[1], kept[2], sizes)
            return (out, dx, again) + tuple(grads)
        got = run()
        assert dispatch.snapshot()['moe_experts'] == 'pallas'
        assert dispatch.grouped_plan_line() == (
            'rows 256x128x256, rows 256x256x128, rows_t 256x128x256, '
            'rows_t 256x256x128, over_rows 256x128x256, '
            'over_rows 256x256x128')
        faults.configure('ops.lowering=error,where=path:pallas')
        want = run()
        assert dispatch.snapshot()['moe_experts'] == 'ragged_dot'
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_fault_at_the_pallas_rung_descends_to_ragged_dot(self):
        from skypilot_tpu.utils import metrics as metrics_lib
        c = metrics_lib.REGISTRY.counter(
            'skyt_ops_kernel_path_total',
            'Kernel dispatch path selected at trace time', ('op', 'path'))
        before = c.value('moe_experts', 'ragged_dot')
        faults.configure('ops.lowering=error,where=path:pallas')
        out = grouped_matmul.expert_ffn(*_chunk())
        assert dispatch.snapshot()['moe_experts'] == 'ragged_dot'
        assert c.value('moe_experts', 'ragged_dot') == before + 1
        assert np.isfinite(np.asarray(out)).all()

    def test_rows_no_tile_divides_descend_too(self):
        """The rule refuses 200 rows; the rung raises at trace time and
        the floor serves the call."""
        x, live, w_gate, w_up, w_down, sizes = _chunk(rows=200)
        sizes = jnp.asarray([50, 0, 90, 30], jnp.int32)
        live = (jnp.arange(200) < 170)[:, None]
        out = grouped_matmul.expert_ffn(x, live, w_gate, w_up, w_down,
                                        sizes)
        assert dispatch.snapshot()['moe_experts'] == 'ragged_dot'
        assert not np.asarray(out[170:]).any()
