"""State DB tests (mirrors reference tests/test_global_user_state.py)."""

from skypilot_tpu import state


class FakeHandle:
    def __init__(self, name):
        self.cluster_name = name
        self.num_hosts = 4
        self.launched_resources = None


class TestClusterState:
    def test_add_get_remove(self, tmp_state_dir):
        state.add_or_update_cluster('c1', FakeHandle('c1'),
                                    status=state.ClusterStatus.UP)
        rec = state.get_cluster('c1')
        assert rec['status'] == state.ClusterStatus.UP
        assert rec['handle'].cluster_name == 'c1'
        state.remove_cluster('c1')   # regression: deadlocked with Lock
        assert state.get_cluster('c1') is None

    def test_relaunch_updates_resources_and_intervals(self, tmp_state_dir):
        state.add_or_update_cluster('c1', FakeHandle('c1'),
                                    requested_resources='r1')
        state.add_or_update_cluster('c1', FakeHandle('c1'),
                                    requested_resources='r2')
        rec = state.get_cluster('c1')
        assert rec['requested_resources'] == 'r2'
        state.remove_cluster('c1')
        hist = state.get_cluster_history()
        (entry,) = [h for h in hist if h['name'] == 'c1']
        # exactly one closed interval despite the double launch
        assert len(entry['usage_intervals']) == 1
        assert entry['usage_intervals'][0][1] is not None

    def test_status_update(self, tmp_state_dir):
        state.add_or_update_cluster('c2', FakeHandle('c2'))
        state.update_cluster_status('c2', state.ClusterStatus.STOPPED)
        assert state.get_cluster('c2')['status'] == \
            state.ClusterStatus.STOPPED

    def test_autostop(self, tmp_state_dir):
        state.add_or_update_cluster('c3', FakeHandle('c3'))
        state.set_cluster_autostop('c3', 30, to_down=True)
        rec = state.get_cluster('c3')
        assert rec['autostop'] == 30 and rec['to_down']

    def test_storage(self, tmp_state_dir):
        state.add_or_update_storage('b1', {'bucket': 'b1'},
                                    state.StorageStatus.READY)
        assert state.get_storage('b1')['status'] == \
            state.StorageStatus.READY
        state.remove_storage('b1')
        assert state.get_storage('b1') is None

    def test_config_kv(self, tmp_state_dir):
        state.set_config('k', {'a': 1})
        assert state.get_config('k') == {'a': 1}
        assert state.get_config('missing', 42) == 42


def test_readers_and_writers_share_the_connection_safely(tmp_state_dir):
    """The module keeps one sqlite connection for the process. A serve
    controller launches its replicas from one thread each, and all of
    them read and write cluster rows at once: a read that does not take
    the module's lock meets another thread's statement on the same
    connection and raises `sqlite3.InterfaceError: bad parameter or
    other API misuse` (seen in a controller's launch thread, which then
    never brought its replica up)."""
    import sys
    import threading
    import time

    errors = []
    stop = time.monotonic() + 1.5

    def work(i):
        name = f'c{i}'
        try:
            while time.monotonic() < stop and not errors:
                state.add_or_update_cluster(
                    name, handle={'i': i}, status=state.ClusterStatus.UP)
                assert state.get_cluster(name)['handle'] == {'i': i}
                assert any(c['name'] == name for c in state.get_clusters())
        except Exception as e:  # pylint: disable=broad-except
            errors.append(repr(e))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert not errors, errors[:3]
