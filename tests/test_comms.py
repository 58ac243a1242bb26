"""Comms plane (docs/observability.md "Comms plane"): link-profile
probe + cache discipline, HLO communication census with mesh-axis
attribution, census × profile estimates, the measurement-driven
placement advisor, and the /fleet/comms route contract."""
import json
import os
import types

import numpy as np
import pytest

from skypilot_tpu.parallel import comms_census
from skypilot_tpu.parallel import comms_profile
from skypilot_tpu.utils import faults
from skypilot_tpu.utils import metrics as metrics_lib


@pytest.fixture()
def comms_cache(tmp_path, monkeypatch):
    path = str(tmp_path / 'comms_profile.json')
    monkeypatch.setenv('SKYT_COMMS_CACHE', path)
    comms_profile.reset_for_tests()
    yield path
    comms_profile.reset_for_tests()


class ScriptedClock:
    """Deterministic monotonic clock: advances a fixed dt per call."""

    def __init__(self, dt: float = 0.001, t: float = 100.0) -> None:
        self.t, self.dt = t, dt

    def __call__(self) -> float:
        self.t += self.dt
        return self.t


class FakeDev:
    def __init__(self, i, slice_index=None):
        self.id = i
        self.device_kind = 'fake'
        if slice_index is not None:
            self.slice_index = slice_index

    def __repr__(self):
        return f'FakeDev({self.id})'


def fake_mesh(shape, axis_names, slice_of=None):
    n = int(np.prod(shape))
    devs = [FakeDev(i, slice_of(i) if slice_of else None)
            for i in range(n)]
    return types.SimpleNamespace(
        devices=np.array(devs, dtype=object).reshape(shape),
        axis_names=tuple(axis_names),
        shape=dict(zip(axis_names, shape)))


# ------------------------------------------------------ cache matrix
class TestProfileCache:
    def test_roundtrip_and_process_restart(self, comms_cache):
        cache = comms_profile.get_cache()
        cache.put('profile|k', {'entries': {'a': {'busbw_gbps': 1.0}}})
        assert os.path.exists(comms_cache)
        # Fresh read from disk = a new process.
        cache.forget_loaded()
        assert cache.get('profile|k')['entries']['a']['busbw_gbps'] \
            == 1.0
        data = json.load(open(comms_cache, encoding='utf-8'))
        assert data['kind'] == 'comms_profile'
        assert data['version'] == 1

    def test_corrupt_cold_start(self, comms_cache):
        with open(comms_cache, 'w', encoding='utf-8') as f:
            f.write('{"version": 1, "entr')   # torn write
        cache = comms_profile.get_cache()
        assert cache.get('profile|k') is None      # no raise
        cache.put('profile|k', {'entries': {}})    # recovers
        cache.forget_loaded()
        assert cache.get('profile|k') == {'entries': {}}

    def test_foreign_layout_cold_start(self, comms_cache):
        # Another cache's file (valid JSON, no comms kind stamp)
        # must read as cold, not as a profile.
        with open(comms_cache, 'w', encoding='utf-8') as f:
            json.dump({'version': 1,
                       'entries': {'x': {'block_q': 256}}}, f)
        assert comms_profile.get_cache().get('x') is None

    def test_unwritable_path_in_memory_only(self, tmp_path):
        comms_profile.reset_for_tests()
        # A directory path: open() for read AND the atomic replace
        # both fail with OSError — load is a cold start, put keeps
        # the in-memory copy and never raises.
        cache = comms_profile.CommsProfileCache(str(tmp_path))
        cache.put('k', {'v': 1})
        assert cache.get('k') == {'v': 1}
        cache.forget_loaded()
        assert cache.get('k') is None   # nothing persisted

    def test_payload_sweep_env(self, monkeypatch):
        monkeypatch.setenv('SKYT_COMMS_PROBE_MB', '0.5, 2,8')
        assert comms_profile.payload_sweep_mb() == [0.5, 2.0, 8.0]
        monkeypatch.setenv('SKYT_COMMS_PROBE_MB', 'nope,-1')
        assert comms_profile.payload_sweep_mb() == \
            list(comms_profile.DEFAULT_PAYLOADS_MB)


# ------------------------------------------------------- link classes
class TestLinkClasses:
    def test_emulated_needs_hint(self):
        mesh = fake_mesh((2, 1, 2), ('dp', 'fsdp', 'tp'))
        assert comms_profile.axis_link_classes(mesh) == \
            {'dp': 'ici', 'tp': 'ici'}
        assert comms_profile.axis_link_classes(mesh, ('dp',)) == \
            {'dp': 'dcn', 'tp': 'ici'}

    def test_slice_index_detection(self):
        # dp-major over 2 slices of 2: walking dp changes slice.
        mesh = fake_mesh((2, 2), ('dp', 'tp'),
                         slice_of=lambda i: i // 2)
        assert comms_profile.axis_link_classes(mesh) == \
            {'dp': 'dcn', 'tp': 'ici'}


# ------------------------------------------------------------- probe
def _fake_bench(mesh, axis, op, payload_mb, iters=5, clock=None):
    # Deterministic synthetic measurement (no jit): bandwidth depends
    # only on (axis, op, payload).
    from skypilot_tpu.parallel import collectives
    n = mesh.shape[axis]
    t = 0.001 * (1 + len(op)) * payload_mb
    payload_bytes = payload_mb * 2 ** 20
    if op in ('all_gather', 'reduce_scatter'):
        payload_bytes *= n
    algbw = payload_bytes / t / 1e9
    return {'op': op, 'axis': axis, 'ranks': n,
            'payload_mb': payload_mb, 'time_ms': t * 1e3,
            'algbw_gbps': algbw,
            'busbw_gbps': algbw * collectives.busbw_factor(op, n)}


class TestProbe:
    def test_probe_deterministic_under_scripted_clock(self, comms_cache):
        mesh = fake_mesh((2, 2), ('dp', 'tp'))
        kw = dict(dcn_axes=('dp',), payloads_mb=[0.25, 1.0],
                  bench=_fake_bench)
        p1 = comms_profile.probe_mesh(mesh, clock=ScriptedClock(), **kw)
        p2 = comms_profile.probe_mesh(mesh, clock=ScriptedClock(), **kw)
        assert p1 == p2
        assert not p1['truncated']
        # 2 axes x 4 ops x 2 payloads
        assert len(p1['entries']) == 16
        e = p1['entries']['all_gather|dp|dcn|r2|mb1']
        assert e['link'] == 'dcn' and e['busbw_gbps'] > 0

    def test_probe_fault_descends_without_crash(self, comms_cache):
        mesh = fake_mesh((2,), ('tp',))
        faults.configure('comms.probe=error,where=op:all_gather')
        try:
            p = comms_profile.probe_mesh(
                mesh, payloads_mb=[1.0], bench=_fake_bench,
                clock=ScriptedClock())
            assert faults.fired_counts()[('comms.probe', 'error')] >= 1
        finally:
            faults.reset()
        ops = {e['op'] for e in p['entries'].values()}
        assert 'all_gather' not in ops
        assert {'all_reduce', 'reduce_scatter', 'ppermute'} <= ops

    def test_probe_budget_truncates_and_skips_persist(self, comms_cache):
        mesh = fake_mesh((2,), ('tp',))
        clock = ScriptedClock(dt=10.0)   # budget gone after one read
        profile, src = comms_profile.load_or_probe(
            mesh, payloads_mb=[1.0], bench=_fake_bench, clock=clock,
            budget_s=5.0)
        assert src == 'probed' and profile['truncated']
        # Truncated profiles must not be cached as the topology truth.
        assert comms_profile.load_cached(mesh) is None

    def test_pair_probe_targets_slice_pairs_not_positions(
            self, comms_cache, monkeypatch):
        """dcn_pairs must be keyed by SLICE index, not merged-axis
        position: a merged dcn-crossing axis with an ICI factor (e.g.
        dp = dcn4 x ici2 = 8) has intra-slice position pairs that are
        ICI hops — probing them as DCN costs would feed the advisor
        wrong bandwidths."""
        calls = []
        monkeypatch.setattr(
            comms_profile, '_probe_dcn_pairs',
            lambda mesh, axis, n_slices, **kw: calls.append(
                (axis, n_slices)) or {'0,1': {'busbw_gbps': 1.0}})
        # Real slices: 4 slices of 2 read off slice_index.
        mesh = fake_mesh((8,), ('dp',), slice_of=lambda i: i // 2)
        p = comms_profile.probe_mesh(mesh, payloads_mb=[1.0],
                                     bench=_fake_bench,
                                     clock=ScriptedClock())
        assert calls == [('dp', 4)]
        assert p['num_slices'] == 4 and p['dcn_pairs']
        # Emulated slices: the caller names the DCN factor.
        calls.clear()
        mesh = fake_mesh((8,), ('dp',))
        comms_profile.probe_mesh(mesh, dcn_axes=('dp',),
                                 payloads_mb=[1.0], num_slices=4,
                                 bench=_fake_bench,
                                 clock=ScriptedClock())
        assert calls == [('dp', 4)]
        # Two slices have no permutation freedom: no pair probe.
        calls.clear()
        p = comms_profile.probe_mesh(fake_mesh((2,), ('dp',)),
                                     dcn_axes=('dp',),
                                     payloads_mb=[1.0],
                                     bench=_fake_bench,
                                     clock=ScriptedClock())
        assert calls == [] and p['dcn_pairs'] == {}

    def test_load_or_probe_caches(self, comms_cache):
        mesh = fake_mesh((2, 2), ('dp', 'tp'))
        p1, src1 = comms_profile.load_or_probe(
            mesh, dcn_axes=('dp',), payloads_mb=[1.0],
            bench=_fake_bench, clock=ScriptedClock())
        assert src1 == 'probed'
        # Fresh process: the cache file answers, no re-probe.
        comms_profile.get_cache().forget_loaded()

        def _boom(*a, **k):
            raise AssertionError('re-probed despite cache hit')
        p2, src2 = comms_profile.load_or_probe(
            mesh, dcn_axes=('dp',), bench=_boom)
        assert src2 == 'cache'
        assert p2['entries'] == p1['entries']


# ------------------------------------------------------------ census
def _entry(op, axes, ranks, payload, count=1):
    return comms_census.CensusEntry(op=op, axes=tuple(axes),
                                    ranks=ranks, payload_bytes=payload,
                                    count=count)


class TestEstimate:
    def test_estimate_math_and_links(self):
        profile = {'entries': {
            'k1': {'op': 'all_gather', 'axis': 'dp', 'link': 'dcn',
                   'ranks': 2, 'payload_mb': 1.0, 'busbw_gbps': 2.0},
            'k2': {'op': 'all_reduce', 'axis': 'tp', 'link': 'ici',
                   'ranks': 2, 'payload_mb': 1.0, 'busbw_gbps': 10.0},
        }}
        entries = [_entry('all_gather', ('dp',), 2, 2 ** 20),
                   _entry('all_reduce', ('tp',), 2, 2 ** 20, count=3)]
        est = comms_census.estimate(entries, profile,
                                    dcn_axes=('dp',))
        # all_gather: payload * (n-1)/n / busbw
        want_dp = 2 ** 20 * 0.5 / 2e9
        assert est['dp']['link'] == 'dcn'
        assert est['dp']['seconds'] == pytest.approx(want_dp)
        assert est['dp']['bytes'] == 2 ** 20
        # all_reduce: payload * 2(n-1)/n / busbw, x3 sites
        want_tp = (2 ** 20) * 1.0 / 10e9 * 3
        assert est['tp']['link'] == 'ici'
        assert est['tp']['seconds'] == pytest.approx(want_tp)
        assert est['tp']['ops']['all_reduce']['count'] == 3

    def test_no_profile_reports_bytes_only(self):
        rep = comms_census.report([_entry('all_reduce', ('dp',), 2,
                                          1024)], 'stablehlo_lowered')
        assert rep['total_bytes'] == 1024
        assert rep['total_seconds'] is None
        assert 'dp' in comms_census.format_report(rep)

    def test_publish_metrics(self):
        reg = metrics_lib.MetricsRegistry()
        rep = comms_census.report(
            [_entry('all_reduce', ('dp',), 2, 1000)],
            'hlo_compiled',
            profile={'entries': {
                'k': {'op': 'all_reduce', 'axis': 'dp', 'link': 'ici',
                      'ranks': 2, 'payload_mb': 1.0,
                      'busbw_gbps': 1.0}}})
        comms_census.publish_metrics(rep, steps=10, registry=reg)
        expo = reg.expose()
        assert ('skyt_train_comm_bytes_total'
                '{axis="dp",op="all_reduce"} 10000') in expo
        assert 'skyt_train_comm_seconds_estimate{axis="dp"}' in expo

    def test_census_mode_env(self, monkeypatch):
        monkeypatch.setenv('SKYT_COMMS_CENSUS', 'off')
        assert comms_census.census_mode() == 'off'
        monkeypatch.setenv('SKYT_COMMS_CENSUS', 'compiled')
        assert comms_census.census_mode() == 'compiled'
        monkeypatch.setenv('SKYT_COMMS_CENSUS', 'bogus')
        assert comms_census.census_mode() == 'lowered'
        monkeypatch.delenv('SKYT_COMMS_CENSUS', raising=False)
        assert comms_census.census_mode() == 'lowered'


class TestCensusParsers:
    def test_hlo_iota_replica_groups(self):
        groups = comms_census._expand_iota_groups(
            4, 2, [2, 2, 2], [0, 2, 1])
        arr = np.arange(8).reshape(2, 2, 2).transpose(0, 2, 1)
        assert groups == arr.reshape(4, 2).tolist()

    def test_hlo_line_census(self):
        mesh = fake_mesh((1, 2, 1, 2, 1, 2),
                         ('pp', 'dp', 'cp', 'fsdp', 'ep', 'tp'))
        line = ('  %all-reduce.1 = f32[4,64]{1,0} all-reduce('
                'f32[4,64]{1,0} %x), channel_id=2, '
                'replica_groups=[4,2]<=[2,2,2]T(0,1,2), '
                'use_global_device_ids=true, to_apply=%add')
        entries = comms_census._census_hlo(line, mesh)
        assert len(entries) == 1
        e = entries[0]
        assert e.op == 'all_reduce' and e.axes == ('tp',)
        assert e.ranks == 2 and e.payload_bytes == 4 * 64 * 4

    def test_hlo_untyped_operands(self):
        """The dump this JAX emits names operands without their types
        ('all-reduce(%fusion.1)'): payloads come from the result type —
        every tuple element of a variadic all-reduce, the result half
        of an async all-gather's (operand, result) tuple, and ranks x
        the result for a reduce-scatter."""
        mesh = fake_mesh((4,), ('fsdp',))
        text = (
            '  %all-reduce.67 = (f32[8,64,32]{2,1,0}, f32[8,64,64]'
            '{2,1,0}) all-reduce(%bitcast.5, %bitcast.8), channel_id=2'
            ', replica_groups=[1,4]<=[4], use_global_device_ids=true, '
            'to_apply=%add.clone\n'
            '  %ags = (f32[4,8]{1,0}, f32[16,8]{1,0}) all-gather-start('
            '%p), channel_id=3, replica_groups=[1,4]<=[4], '
            'dimensions={0}\n'
            '  %rs = f32[2,8]{1,0} reduce-scatter(%x), channel_id=4, '
            'replica_groups=[1,4]<=[4], dimensions={0}, '
            'to_apply=%add\n')
        got = {e.op: e.payload_bytes
               for e in comms_census._census_hlo(text, mesh)}
        assert got == {'all_reduce': (8 * 64 * 32 + 8 * 64 * 64) * 4,
                       'all_gather': 16 * 8 * 4,
                       'reduce_scatter': 2 * 8 * 4 * 4}

    def test_hlo_done_ops_skipped(self):
        mesh = fake_mesh((2,), ('dp',))
        text = ('  %ag = f32[8]{0} all-gather-start(f32[4]{0} %x), '
                'replica_groups={{0,1}}, dimensions={0}\n'
                '  %agd = f32[8]{0} all-gather-done(f32[8]{0} %ag)\n')
        entries = comms_census._census_hlo(text, mesh)
        assert len(entries) == 1 and entries[0].op == 'all_gather'
        assert entries[0].payload_bytes == 8 * 4   # gathered buffer

    def test_collective_permute_pairs(self):
        mesh = fake_mesh((2, 2), ('dp', 'tp'))
        line = ('  %cp = f32[4]{0} collective-permute(f32[4]{0} %x), '
                'channel_id=1, source_target_pairs={{0,2},{2,0}}')
        (e,) = comms_census._census_hlo(line, mesh)
        assert e.op == 'collective_permute' and e.axes == ('dp',)


# --------------------------------------------------- advisor/placement
HET_PAIRS = {   # slow links on (0,3) and (1,2); everything else fast
    '0,1': {'busbw_gbps': 10.0}, '0,2': {'busbw_gbps': 10.0},
    '0,3': {'busbw_gbps': 1.0}, '1,2': {'busbw_gbps': 1.0},
    '1,3': {'busbw_gbps': 10.0}, '2,3': {'busbw_gbps': 10.0}}
HET_PROFILE = {'entries': {}, 'dcn_pairs': HET_PAIRS}


class TestPlacementAdvisor:
    def test_picks_cheap_permutation(self):
        dec = comms_profile.choose_dcn_permutation(4, HET_PROFILE)
        # The only 4-ring avoiding both slow links is 0-1-3-2(-0).
        assert dec['perm'] == [0, 1, 3, 2]
        assert dec['score'] == pytest.approx(4 * 0.1)
        assert dec['rowmajor_score'] == pytest.approx(0.1 + 1 + 0.1 + 1)
        assert dec['score'] < dec['rowmajor_score']

    def test_no_profile_keeps_rowmajor_order(self):
        dec = comms_profile.choose_dcn_permutation(4, None)
        assert dec['perm'] == [0, 1, 2, 3]

    def test_two_slices_identity(self):
        dec = comms_profile.choose_dcn_permutation(2, HET_PROFILE)
        assert dec['perm'] == [0, 1]

    def test_cached_across_restart(self, comms_cache):
        # Production shape: the probed profile sits in the same cache
        # under its topology key; the placement winner is valid as
        # long as the profile it was scored against is.
        comms_profile.get_cache().put('profile|k', HET_PROFILE)
        perm = comms_profile.placement_for('k#spec', 4, HET_PROFILE)
        assert perm == [0, 1, 3, 2]
        comms_profile.get_cache().forget_loaded()
        # No profile handed in: cached profile + cached winner answer.
        assert comms_profile.placement_for('k#spec', 4) == [0, 1, 3, 2]

    def test_new_profile_invalidates_cached_winner(self, comms_cache):
        assert comms_profile.placement_for(
            'k#spec', 4, HET_PROFILE) == [0, 1, 3, 2]
        # Re-measured network: the slow links moved to the old cheap
        # ring's hops — the cached winner must NOT outlive the probe.
        flipped = {'entries': {}, 'dcn_pairs': {
            k: {'busbw_gbps': 11.0 - v['busbw_gbps']}
            for k, v in HET_PAIRS.items()}}
        perm2 = comms_profile.placement_for('k#spec', 4, flipped)
        assert perm2 == [0, 1, 2, 3]

    def test_bad_cached_entry_recomputes(self, comms_cache):
        comms_profile.get_cache().put('placement|k#spec',
                                      {'perm': [7, 7]})
        assert comms_profile.placement_for('k#spec', 4, HET_PROFILE) \
            == [0, 1, 3, 2]


@pytest.mark.heavy
class TestHybridMeshPlacement:
    def test_rowmajor_byte_identical_and_default(self, comms_cache):
        import jax

        from skypilot_tpu.parallel import mesh as mesh_lib
        ici = mesh_lib.MeshSpec(fsdp=2, tp=2)
        dcn = mesh_lib.MeshSpec(dp=2)
        base = mesh_lib.build_hybrid_mesh(ici, dcn, num_slices=2)
        explicit = mesh_lib.build_hybrid_mesh(ici, dcn, num_slices=2,
                                              placement='rowmajor')
        # Expected row-major chunk-interleave layout, computed
        # independently of build_hybrid_mesh: device order is
        # dp-major over contiguous 4-device slices, fsdp then tp
        # within a slice.
        want = np.array(jax.devices()[:8]).reshape(1, 2, 1, 2, 1, 2)
        for mesh in (base, explicit):
            assert (np.vectorize(id)(mesh.devices) ==
                    np.vectorize(id)(want)).all()

    def test_bad_placement_raises(self):
        from skypilot_tpu.parallel import mesh as mesh_lib
        with pytest.raises(ValueError, match='placement'):
            mesh_lib.build_hybrid_mesh(
                mesh_lib.MeshSpec(tp=4), mesh_lib.MeshSpec(dp=2),
                num_slices=2, placement='fancy')

    def test_measured_applies_cheap_slice_order(self, comms_cache):
        import jax

        from skypilot_tpu.parallel import mesh as mesh_lib
        mesh = mesh_lib.build_hybrid_mesh(
            mesh_lib.MeshSpec(tp=2), mesh_lib.MeshSpec(dp=4),
            num_slices=4, placement='measured', profile=HET_PROFILE)
        got = [d.id for d in mesh.devices.reshape(-1)]
        # Slice groups [0,1],[2,3],[4,5],[6,7] in advisor order
        # [0, 1, 3, 2].
        assert got == [0, 1, 2, 3, 6, 7, 4, 5]
        # ICI layout inside each slice untouched: tp pairs stay
        # contiguous chunks.
        arr = mesh.devices
        for dpi in range(4):
            pair = [arr[0, dpi, 0, 0, 0, t].id for t in range(2)]
            assert pair[1] == pair[0] + 1

    def test_real_pair_probe_crosses_slice_boundaries(self,
                                                      comms_cache):
        """Real _probe_dcn_pairs on an 8-device dp axis with a
        4-slice DCN factor: 6 slice pairs (not 28 position pairs)."""
        import jax

        from skypilot_tpu.parallel import mesh as mesh_lib
        mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(dp=8),
                                   jax.devices()[:8])
        pairs = comms_profile._probe_dcn_pairs(
            mesh, 'dp', 4, payload_mb=0.25, iters=1)
        assert sorted(pairs) == ['0,1', '0,2', '0,3', '1,2', '1,3',
                                 '2,3']
        assert all(v['busbw_gbps'] > 0 for v in pairs.values())

    def test_measured_without_profile_matches_rowmajor(self,
                                                       comms_cache):
        from skypilot_tpu.parallel import mesh as mesh_lib
        ici, dcn = mesh_lib.MeshSpec(tp=2), mesh_lib.MeshSpec(dp=4)
        row = mesh_lib.build_hybrid_mesh(ici, dcn, num_slices=4)
        measured = mesh_lib.build_hybrid_mesh(ici, dcn, num_slices=4,
                                              placement='measured')
        assert (np.vectorize(id)(row.devices) ==
                np.vectorize(id)(measured.devices)).all()


# ------------------------------------------- census on real programs
@pytest.mark.heavy
class TestCensusReal:
    def test_shardmap_lowered_census(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from skypilot_tpu.parallel import mesh as mesh_lib
        mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(dp=2, fsdp=2,
                                                     tp=2))

        def f(x):
            y = jax.lax.psum(x, 'tp')
            z = jax.lax.all_gather(x, 'fsdp')
            w = jax.lax.ppermute(x, 'dp', [(0, 1), (1, 0)])
            s = jax.lax.psum_scatter(x, 'tp', tiled=True)
            return (jnp.sum(y) + jnp.sum(z) + jnp.sum(w) +
                    jnp.sum(s[..., :1]))

        fn = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P('dp'),
                                   out_specs=P(), check_vma=False))
        x = jnp.ones((8, 4))
        entries, source = comms_census.census_step(fn, x, mesh=mesh)
        assert source == 'stablehlo_lowered'
        by_op = {e.op: e for e in entries}
        assert by_op['all_reduce'].axes == ('tp',)
        assert by_op['all_gather'].axes == ('fsdp',)
        assert by_op['collective_permute'].axes == ('dp',)
        assert by_op['reduce_scatter'].axes == ('tp',)
        # Per-shard payloads: x is [8,4] f32 over dp=2 -> [4,4].
        assert by_op['all_reduce'].payload_bytes == 4 * 4 * 4
        assert by_op['all_gather'].payload_bytes == 2 * 4 * 4 * 4

    @pytest.mark.parametrize('axis', ['dp', 'fsdp', 'tp'])
    def test_tiny_llama_census_attributes_right_axis(self, axis):
        """Golden counts on the tiny llama: with exactly one active
        mesh axis, every SPMD-inserted collective must attribute to
        that axis (compiled mode — pjit collectives don't exist at
        the lowered stage)."""
        import jax
        import jax.numpy as jnp

        from skypilot_tpu.models import llama
        from skypilot_tpu.parallel import mesh as mesh_lib
        from skypilot_tpu.train import trainer

        mesh = mesh_lib.build_mesh(
            mesh_lib.MeshSpec(**{axis: 2}), jax.devices()[:2])
        cfg = llama.CONFIGS['debug']
        model = llama.LlamaModel(cfg)
        tx = trainer.make_optimizer(trainer.TrainerConfig(
            warmup_steps=1, total_steps=4))
        sample = jnp.zeros((4, 64), jnp.int32)
        state, _ = trainer.create_sharded_state(
            model, tx, mesh, sample, jax.random.PRNGKey(0))
        step = trainer.make_train_step(model, tx, mesh, donate=False)
        data = {'tokens': sample, 'targets': sample}
        # Lowered mode on a pjit program: zero collectives, by design.
        low_entries, low_src = comms_census.census_step(
            step, state, data, mesh=mesh, mode='lowered')
        assert low_src == 'stablehlo_lowered' and low_entries == []
        entries, source = comms_census.census_step(
            step, state, data, mesh=mesh, mode='compiled')
        assert source == 'hlo_compiled'
        assert entries, 'SPMD inserted no collectives?'
        assert all(e.axes == (axis,) for e in entries), entries
        rep = comms_census.report(entries, source)
        assert rep['axes'][axis]['bytes'] > 0
        ops = set(rep['axes'][axis]['ops'])
        # Gradient sync rides all-reduce on every spec; fsdp's
        # parameter gathering adds all-gather.
        assert 'all_reduce' in ops
        if axis == 'fsdp':
            assert 'all_gather' in ops

    def test_pipeline_pp_census(self):
        import jax
        import jax.numpy as jnp

        from skypilot_tpu.parallel import mesh as mesh_lib
        from skypilot_tpu.parallel import pipeline

        pp = 4
        mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(pp=pp),
                                   jax.devices()[:pp])
        dim, m, bm = 8, 8, 2

        def stage_fn(params, x):
            return jnp.tanh(x @ params['w'])

        stacked = {'w': jnp.ones((pp, dim, dim)) * 0.1}
        batch = jnp.ones((m * bm, dim))
        targets = jnp.zeros_like(batch)
        loss_fn = pipeline.pipeline_loss_fn(
            stage_fn, lambda y, t: jnp.mean((y - t) ** 2), mesh,
            num_microbatches=m)
        grad_fn = jax.jit(jax.value_and_grad(loss_fn))
        entries, source = comms_census.census_step(
            grad_fn, stacked, batch, targets, mesh=mesh)
        assert source == 'stablehlo_lowered'
        ops = {e.op for e in entries}
        assert 'collective_permute' in ops   # the stage ring
        assert all(e.axes == ('pp',) for e in entries), entries


# --------------------------------------------------- /fleet/comms
EXPO_T0 = """\
# TYPE skyt_comms_probe_busbw_gbps gauge
skyt_comms_probe_busbw_gbps{axis="dp",op="all_gather",link="dcn"} 0.8
skyt_comms_probe_busbw_gbps{axis="tp",op="all_reduce",link="ici"} 42.0
# TYPE skyt_train_comm_seconds_estimate gauge
skyt_train_comm_seconds_estimate{axis="dp"} 0.0031
# TYPE skyt_train_comm_bytes_total counter
skyt_train_comm_bytes_total{axis="dp",op="all_gather"} 1000
"""
EXPO_T1 = EXPO_T0.replace(
    'skyt_train_comm_bytes_total{axis="dp",op="all_gather"} 1000',
    'skyt_train_comm_bytes_total{axis="dp",op="all_gather"} 61000')


class TestFleetComms:
    def _fleet(self, comms_cache):
        from skypilot_tpu.serve import fleet as fleet_lib

        class Clock:
            t = 1_000_000.0

            def __call__(self):
                return self.t
        clock = Clock()
        fl = fleet_lib.FleetTelemetry(
            'svc', metrics_registry=metrics_lib.MetricsRegistry(),
            clock=clock,
            http_get=lambda url, t: EXPO_T0)
        fl.ingest_text('r1', EXPO_T0)
        clock.t += 30
        fl.ingest_text('r1', EXPO_T1)
        return fl

    def test_comms_report(self, comms_cache):
        fl = self._fleet(comms_cache)
        rep = fl.comms_report(window_s=600)
        t = rep['targets']['r1']
        assert t['probe_busbw_gbps']['dp|all_gather|dcn'] == 0.8
        assert t['comm_seconds_estimate']['dp'] == 0.0031
        assert t['comm_bytes_per_s']['dp'] == pytest.approx(
            60000 / 600)
        # The local cached profile summary rides along.
        comms_profile.get_cache().put('profile|fake|d2|tp2i', {
            'entries': {'k': {'op': 'all_reduce', 'axis': 'tp',
                              'link': 'ici', 'ranks': 2,
                              'payload_mb': 1.0, 'busbw_gbps': 5.0}}})
        rep = fl.comms_report(window_s=600)
        assert rep['local_profiles']['fake|d2|tp2i'][
            'ici.all_reduce']['busbw_gbps'] == 5.0

    def test_route_contract(self, comms_cache):
        import asyncio

        from aiohttp import web
        from aiohttp.test_utils import TestClient, TestServer

        from skypilot_tpu.serve import fleet as fleet_lib
        fl = self._fleet(comms_cache)

        async def run():
            app = web.Application()
            fleet_lib.add_fleet_routes(app, fl, lambda rid: None)
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                resp = await client.get('/fleet/comms')
                assert resp.status == 200
                body = await resp.json()
                assert body['service'] == 'svc'
                assert 'r1' in body['targets']
                assert body['targets']['r1'][
                    'probe_busbw_gbps']['tp|all_reduce|ici'] == 42.0
                resp = await client.get('/fleet/comms',
                                        params={'window_s': '-3'})
                assert resp.status == 400
            finally:
                await client.close()

        asyncio.run(run())


# -------------------------------------------------- collectives CLI
@pytest.mark.heavy
class TestCollectivesCli:
    def test_json_artifact_ok(self, tmp_path):
        from skypilot_tpu.parallel import collectives
        out = tmp_path / 'collectives.json'
        collectives.main(['--axis', 'tp', '--mb', '0.05', '--iters',
                          '2', '--ops', 'all_reduce', '--json',
                          str(out)])
        data = json.loads(out.read_text())
        assert data['status'] == 'ok'
        assert data['payload_mib'] == 0.05
        (r,) = data['results']
        assert r['op'] == 'all_reduce' and r['ranks'] == 8
        assert r['busbw_gbps'] > 0

    def test_mib_payload_rounding(self):
        """bench_collective sizes payloads in MiB: 1 MiB over 8 ranks
        = 2**20/4 f32 elements, rounded to a multiple of n."""
        import jax

        from skypilot_tpu.parallel import collectives
        from skypilot_tpu.parallel import mesh as mesh_lib
        mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(tp=2),
                                   jax.devices()[:2])
        r = collectives.bench_collective(mesh, 'tp', 'ppermute',
                                         payload_mb=1.0, iters=1)
        # per-rank buffer for ppermute = elems*4 bytes = 1 MiB exactly
        # (2**20/4 divisible by 2).
        assert r['payload_mb'] == 1.0
        assert r['algbw_gbps'] * r['time_ms'] * 1e6 == pytest.approx(
            2 ** 20, rel=1e-6)


# -------------------------------------------------------- sft e2e
@pytest.mark.heavy
def test_sft_logs_comms_census_on_hybrid_mesh(tmp_path, monkeypatch):
    """CPU end-to-end acceptance: a multislice (emulated 2-slice) sft
    run logs the per-axis comms breakdown next to MFU, publishes the
    comm metric families, and lands the report in the postmortem live
    state / train.steps span attrs path."""
    import io
    import logging

    monkeypatch.setenv('SKYT_COMMS_CACHE',
                       str(tmp_path / 'comms.json'))
    monkeypatch.setenv('SKYT_COMMS_CENSUS', 'compiled')
    monkeypatch.setenv('SKYT_WATCHDOG', '0')
    comms_profile.reset_for_tests()
    from skypilot_tpu.train import sft

    # The framework logger does not propagate to pytest's caplog
    # handler; attach one directly.
    buf = io.StringIO()
    handler = logging.StreamHandler(buf)
    sft.logger.addHandler(handler)
    try:
        sft.main(['--model', 'debug', '--mesh', 'fsdp=2,tp=2',
                  '--dcn-mesh', 'dp=2', '--steps', '2', '--batch',
                  '4', '--seq', '64', '--log-every', '1',
                  '--prefetch', '0'])
    finally:
        sft.logger.removeHandler(handler)
    text = buf.getvalue()
    assert 'comms census (hlo_compiled' in text
    assert 'dcn' in text.split('comms census')[1].splitlines()[0]
    expo = metrics_lib.REGISTRY.expose()
    assert 'skyt_train_comm_bytes_total{axis="' in expo
    comms_profile.reset_for_tests()
