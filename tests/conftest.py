"""Shared fixtures. NOTE: no XLA_FLAGS here — tests must see the real
(single) device; only launch/dryrun.py forces 512 placeholder devices."""
import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _pool_invariants():
    """After every test, sweep every live `PagedKVPool`, `PagedKVState`
    and `DevicePagePool` (weak registries) and assert their structural
    invariants: refcounts match holders, cached page-table rows match the
    pool, free lists are disjoint from live slots, per-tier byte stats
    are consistent. A test that corrupts
    pool state fails HERE with the invariant message even if its own
    assertions passed — serve-suite teardown coverage for free."""
    yield
    from repro.serve.device_pool import DevicePagePool
    from repro.serve.kvcache import PagedKVPool
    from repro.serve.paged_decode import PagedKVState
    from repro.serve.paged_state import RecurrentStore
    for pool in list(PagedKVPool._instances):
        pool.check_invariants()
    for state in list(PagedKVState._instances):
        state.check_invariants()
    for dev in list(DevicePagePool._instances):
        dev.check_invariants()
    for store in list(RecurrentStore._instances):
        store.check_invariants()
