"""Many-model serving: `ModelRegistry` (versioned catalog, bit-identical
round-trip), `ThetaStore` (LRU paging, pinned slots, fault/writeback), and
the multi-tenant `KernelServer` (gathered bucket scoring, hot-swap
atomicity, request-lifecycle hardening).

Bit-level contract: a multi-tenant server's answer for a tagged request is
`KernelModel.score_rows(x, theta_rows)` — the gathered per-row matvec,
which is row-stable for b >= 2 (a request's rows score identically no
matter which other tenants share its padded bucket) and within float
reduction-order (~1e-6) of `KernelModel.predict`.
"""
import dataclasses
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import FitConfig, KernelModel, KRRConfig, fit
from repro.serve import (KernelServeConfig, KernelServer, ModelRegistry,
                         ThetaStore)

BASE = FitConfig(
    krr=KRRConfig(num_agents=4, samples_per_agent=30, num_features=16,
                  lam=1e-2, rho=0.5, seed=0),
    algorithm="coke", censor_v=0.5, censor_mu=0.97, num_iters=30)


@pytest.fixture(scope="module")
def base_model():
    return fit(BASE).to_model()


def variant(base: KernelModel, i: int) -> KernelModel:
    """A per-user model: the base artifact with a perturbed theta (what a
    per-user `partial_fit` refinement produces, without the fit cost)."""
    rng = np.random.default_rng(1000 + i)
    theta = np.asarray(base.theta) + rng.normal(
        scale=0.1, size=base.num_features).astype(np.float32)
    return dataclasses.replace(base, theta=jnp.asarray(theta), thetas=None)


def rowwise_ref(model: KernelModel, x: np.ndarray, theta,
                bucket: int | None = None) -> np.ndarray:
    """The bit-level serving reference: score_rows with x's rows all tagged
    to one theta. With `bucket`, x is zero-padded to that many rows first,
    so the reference runs at the same batch shape as the server's device
    call: XLA's CPU backend vectorizes the length-D row reduction
    differently below 128 rows than at 128 and above, and a 2-row
    score_rows then differs from a 32-row bucket by 1 ulp in some rows."""
    b = x.shape[0]
    if bucket is not None:
        x = np.concatenate([x, np.zeros((bucket - b, x.shape[1]), x.dtype)])
    rows = np.broadcast_to(np.asarray(theta),
                           (x.shape[0], model.num_features))
    return np.asarray(model.score_rows(x, rows))[:b]


@pytest.fixture(scope="module")
def registry8(tmp_path_factory, base_model):
    reg = ModelRegistry(str(tmp_path_factory.mktemp("registry")))
    for i in range(8):
        reg.publish(f"user-{i}", variant(base_model, i))
    return reg


@pytest.fixture(scope="module")
def queries(base_model):
    rng = np.random.default_rng(7)
    return rng.uniform(size=(64, base_model.input_dim)).astype(np.float32)


# ---------------------------------------------------------------------------
# ModelRegistry
# ---------------------------------------------------------------------------

def test_registry_publish_load_roundtrips_bit_identically(tmp_path,
                                                          base_model):
    reg = ModelRegistry(str(tmp_path))
    m = variant(base_model, 0)
    v = reg.publish("alice", m)
    assert v == 1
    loaded = reg.load("alice")
    np.testing.assert_array_equal(np.asarray(loaded.theta),
                                  np.asarray(m.theta))
    np.testing.assert_array_equal(np.asarray(loaded.rff_params.omega),
                                  np.asarray(m.rff_params.omega))
    np.testing.assert_array_equal(np.asarray(loaded.rff_params.bias),
                                  np.asarray(m.rff_params.bias))
    # identity is stamped on publish and survives the round trip
    assert loaded.model_id == "alice" and loaded.version == 1
    assert loaded.meta == m.meta
    # predictions are therefore bit-identical too
    rng = np.random.default_rng(0)
    x = rng.uniform(size=(8, m.input_dim)).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(loaded.predict(x)),
                                  np.asarray(m.predict(x)))
    # a version dir is itself a plain KernelModel artifact
    direct = KernelModel.load(reg.artifact_path("alice", 1))
    np.testing.assert_array_equal(np.asarray(direct.theta),
                                  np.asarray(m.theta))


def test_registry_versions_and_latest(tmp_path, base_model):
    reg = ModelRegistry(str(tmp_path))
    thetas = []
    for i in range(3):
        m = variant(base_model, i)
        thetas.append(np.asarray(m.theta))
        assert reg.publish("bob", m) == i + 1
    assert reg.versions("bob") == [1, 2, 3]
    assert reg.latest_version("bob") == 3
    assert reg.models() == ["bob"]
    assert "bob" in reg and "carol" not in reg
    np.testing.assert_array_equal(np.asarray(reg.load("bob").theta),
                                  thetas[2])
    np.testing.assert_array_equal(np.asarray(reg.load("bob", 2).theta),
                                  thetas[1])
    with pytest.raises(KeyError):
        reg.load("carol")
    with pytest.raises(KeyError):
        reg.load("bob", 9)
    # versions are immutable
    with pytest.raises(ValueError, match="immutable"):
        reg.publish("bob", variant(base_model, 9), version=2)


def test_registry_rejects_bad_ids(tmp_path, base_model):
    reg = ModelRegistry(str(tmp_path))
    for bad in ("", "a/b", "../up", ".hidden", "sp ace"):
        with pytest.raises(ValueError, match="model id"):
            reg.publish(bad, base_model)


# ---------------------------------------------------------------------------
# ThetaStore
# ---------------------------------------------------------------------------

def _theta(d, i):
    return np.full(d, float(i), np.float32)


def test_theta_store_lru_eviction_order():
    store = ThetaStore(3, 4)
    for name in ("a", "b", "c"):
        store.put(name, _theta(4, ord(name)))
    store.ensure("a")                      # a becomes most-recently-used
    store.put("d", _theta(4, 1))           # evicts b: the LRU entry
    assert store.resident() == ["c", "a", "d"]
    assert "b" not in store
    assert store.stats()["evictions"] == 1
    # the surviving slots still hold their exact thetas
    stack, slots, errors = store.lookup_batch(["a", "c", "d"])
    assert errors == [None, None, None]
    np.testing.assert_array_equal(np.asarray(stack[slots[0]]),
                                  _theta(4, ord("a")))


def test_theta_store_pinned_slot_protected():
    store = ThetaStore(2, 4)
    store.put("a", _theta(4, 1))
    store.put("b", _theta(4, 2))
    store.ensure("a")                      # a is MRU; b is the LRU victim...
    store.pin("b")                         # ...but pinned
    store.put("c", _theta(4, 3))           # must evict a instead
    assert "b" in store and "a" not in store
    store.pin("c")
    with pytest.raises(RuntimeError, match="pinned"):
        store.put("d", _theta(4, 4))       # every slot pinned
    store.unpin("b")
    store.put("d", _theta(4, 4))           # now b can go
    assert "d" in store and "b" not in store
    with pytest.raises(RuntimeError, match="not pinned"):
        store.unpin("b")


def test_theta_store_fault_and_dirty_writeback():
    backing = {"x": (np.full(4, 9.0, np.float32), 3)}
    published = {}

    def fault(mid):
        if mid not in backing:
            raise KeyError(mid)
        return backing[mid]

    def writeback(mid, theta, version):
        published[mid] = (np.asarray(theta), version)
        return (version or 0) + 1

    store = ThetaStore(1, 4, fault=fault, writeback=writeback)
    assert store.ensure("x") >= 0          # faulted in
    assert store.version_of("x") == 3
    assert store.stats()["faults"] == 1
    with pytest.raises(KeyError):
        store.ensure("nope")
    # a dirty resident pages back to the registry on eviction
    store.put("dirty", np.full(4, 5.0, np.float32), dirty=True)  # evicts x
    store.ensure("x")                      # evicts dirty -> writeback
    np.testing.assert_array_equal(published["dirty"][0],
                                  np.full(4, 5.0, np.float32))
    assert store.stats()["writebacks"] == 1
    # without a writeback, evicting a dirty model refuses to lose it
    lone = ThetaStore(1, 4)
    lone.put("only", np.full(4, 1.0, np.float32), dirty=True)
    with pytest.raises(RuntimeError, match="dirty"):
        lone.put("next", np.full(4, 2.0, np.float32))


def test_theta_store_shape_validation():
    store = ThetaStore(2, 4)
    with pytest.raises(ValueError, match="theta"):
        store.put("a", np.zeros(5, np.float32))
    with pytest.raises(ValueError, match="capacity"):
        ThetaStore(0, 4)


def test_theta_stack_spec_shards_feature_dim():
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import theta_stack_spec
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh()  # 1x1: "model" extent 1 divides everything
    assert theta_stack_spec((8, 16), mesh) == P(None, "model")
    assert theta_stack_spec((8, 16, 3), mesh) == P(None, None, "model")


# ---------------------------------------------------------------------------
# multi-tenant KernelServer
# ---------------------------------------------------------------------------

def test_multi_tenant_gather_parity_under_paging(base_model, registry8,
                                                 queries):
    """Tagged requests through a store FORCED smaller than the tenant set:
    every answer is bit-identical to the row-wise reference with that
    tenant's registry theta, and within reduction-order of its
    `KernelModel.predict`. One bucket shape, so the reference can run at
    the server's own batch shape (see `rowwise_ref`)."""
    rng = np.random.default_rng(3)
    server = KernelServer(
        model=base_model, registry=registry8,
        store=ThetaStore(4, base_model.num_features),
        config=KernelServeConfig(max_delay_ms=5.0, max_batch=128,
                                 buckets=(128,)), autostart=False)
    reqs = []
    for i in range(20):
        mid = f"user-{rng.integers(0, 8)}"
        b = int(rng.integers(2, 6))
        x = queries[:b] + np.float32(0.01) * i
        reqs.append((mid, x, server.submit(x, mid)))
    server.start()
    outs = [(mid, x, np.asarray(f.result())) for mid, x, f in reqs]
    server.stop()
    assert server.stats()["store"]["faults"] > 0  # paging actually happened
    for mid, x, out in outs:
        theta = registry8.load(mid).theta
        np.testing.assert_array_equal(
            out, rowwise_ref(base_model, x, theta, bucket=128))
        np.testing.assert_allclose(
            out, np.asarray(registry8.load(mid).predict(x)), atol=2e-6)


def test_thousand_resident_models_bit_parity(base_model, queries):
    """The acceptance-scale contract: one server, >= 1000 resident models
    in one (M, D) stack, every tagged answer bit-identical to its model's
    row-wise reference — through bucket-padded gathered device calls, all
    of one bucket shape so the reference runs at the same batch shape."""
    M, D = 1000, base_model.num_features
    rng = np.random.default_rng(11)
    thetas = rng.normal(scale=0.2, size=(M, D)).astype(np.float32)
    ids = [f"u{i:04d}" for i in range(M)]
    store = ThetaStore(1024, D)
    store.put_many(ids, thetas)
    server = KernelServer(model=base_model, store=store,
                          config=KernelServeConfig(max_delay_ms=5.0,
                                                   max_batch=128,
                                                   buckets=(128,)),
                          autostart=False)
    assert len(store) >= 1000
    picks = rng.integers(0, M, size=100)
    futs = [server.submit(queries[j % 32:j % 32 + 2], ids[i])
            for j, i in enumerate(picks)]
    server.start()
    outs = [np.asarray(f.result()) for f in futs]
    server.stop()
    for j, (i, out) in enumerate(zip(picks, outs)):
        x = queries[j % 32:j % 32 + 2]
        np.testing.assert_array_equal(
            out, rowwise_ref(base_model, x, thetas[i], bucket=128))


def test_answer_independent_of_cobatched_tenants(base_model, registry8,
                                                 queries):
    """Row-stability contract: the same (x, model) request scores
    bit-identically whether it is flushed alone or coalesced into a mixed
    bucket with other tenants."""
    x = queries[:3]
    with KernelServer(model=base_model, registry=registry8,
                      config=KernelServeConfig(max_delay_ms=0.0)) as server:
        alone = np.asarray(server.predict(x, "user-3"))
    server = KernelServer(model=base_model, registry=registry8,
                          config=KernelServeConfig(max_delay_ms=5.0),
                          autostart=False)
    futs = [server.submit(queries[4 * i:4 * i + 4], f"user-{i}")
            for i in range(6)]
    probe = server.submit(x, "user-3")
    server.start()
    for f in futs:
        f.result()
    cobatched = np.asarray(probe.result())
    server.stop()
    np.testing.assert_array_equal(alone, cobatched)


def test_publish_hot_swaps_for_subsequent_requests(base_model, registry8,
                                                   queries, tmp_path):
    reg = ModelRegistry(str(tmp_path))
    reg.publish("solo", variant(base_model, 0))
    x = queries[:4]
    with KernelServer(model=base_model, registry=reg) as server:
        before = np.asarray(server.predict(x, "solo"))
        refined = np.asarray(variant(base_model, 5).theta)
        v = server.publish("solo", refined)
        assert v == 2 and reg.latest_version("solo") == 2
        after = np.asarray(server.predict(x, "solo"))
    np.testing.assert_array_equal(
        before, rowwise_ref(base_model, x, reg.load("solo", 1).theta))
    np.testing.assert_array_equal(after,
                                  rowwise_ref(base_model, x, refined))
    assert not np.array_equal(before, after)
    # the registry artifact round-trips the refined theta bit-identically
    np.testing.assert_array_equal(np.asarray(reg.load("solo").theta),
                                  refined)


def test_hot_swap_atomicity_under_fire(base_model, registry8, queries):
    """No request ever scores a torn theta: while publishes hammer one
    tenant, every concurrent answer equals EXACTLY one published version's
    reference — never a mixture — and every in-flight future resolves."""
    reg_theta = np.asarray(registry8.load("user-0").theta)
    versions = [reg_theta] + [
        reg_theta + np.float32(0.5) * (k + 1) for k in range(8)]
    x = queries[:4]
    refs = [rowwise_ref(base_model, x, th) for th in versions]
    server = KernelServer(model=base_model, registry=registry8,
                          config=KernelServeConfig(max_delay_ms=0.5))
    results, failures = [], []

    def client():
        for _ in range(30):
            try:
                results.append(np.asarray(
                    server.submit(x, "user-0").result(timeout=30)))
            except Exception as e:  # noqa: BLE001 - recorded and asserted
                failures.append(e)

    threads = [threading.Thread(target=client) for _ in range(4)]
    for t in threads:
        t.start()
    for th in versions[1:]:
        server.publish("user-0", th)
    for t in threads:
        t.join()
    server.stop()
    assert not failures
    assert len(results) == 120
    for out in results:
        assert any(np.array_equal(out, ref) for ref in refs), \
            "a served answer matched no published theta: torn read"


# ---------------------------------------------------------------------------
# request-lifecycle hardening
# ---------------------------------------------------------------------------

def test_unknown_model_fails_its_future_only(base_model, registry8,
                                             queries):
    with KernelServer(model=base_model, registry=registry8) as server:
        bad = server.submit(queries[:2], "nobody")
        with pytest.raises(KeyError, match="nobody"):
            bad.result(timeout=10)
        # the collector survived; tagged traffic keeps flowing
        out = server.predict(queries[:2], "user-1")
        np.testing.assert_array_equal(
            out, rowwise_ref(base_model, queries[:2],
                             registry8.load("user-1").theta))


def test_wrong_input_dim_raises_before_enqueue(base_model, registry8):
    with KernelServer(model=base_model, registry=registry8) as server:
        with pytest.raises(ValueError, match="queries"):
            server.submit(np.zeros((2, 99), np.float32), "user-1")
        before = server.stats()["requests"]
    assert before == 0  # the bad request never reached the queue


def test_stopped_multi_tenant_server_rejects_submissions(base_model,
                                                         registry8,
                                                         queries):
    server = KernelServer(model=base_model, registry=registry8)
    server.predict(queries[:2], "user-1")
    server.stop()
    with pytest.raises(RuntimeError, match="stopped"):
        server.submit(queries[:2], "user-1")


def test_single_tenant_server_rejects_foreign_model_ids(base_model,
                                                        queries):
    with KernelServer(base_model) as server:
        with pytest.raises(ValueError, match="many-model"):
            server.submit(queries[:2], "someone-else")


def test_multi_tenant_construction_contracts(base_model, registry8,
                                             tmp_path):
    # publish() is a multi-tenant feature
    with KernelServer(base_model) as single:
        with pytest.raises(RuntimeError, match="multi-tenant"):
            single.publish("x", base_model.theta)
    # an empty registry cannot define the featurizer template
    with pytest.raises(ValueError, match="registry"):
        KernelServer(registry=ModelRegistry(str(tmp_path)))
    # a store sized for a different D is rejected
    with pytest.raises(ValueError, match="D="):
        KernelServer(model=base_model,
                     store=ThetaStore(4, base_model.num_features + 1))
    # a tenant fitted against a different featurizer is rejected
    other = fit(BASE.replace(
        krr=dataclasses.replace(BASE.krr, seed=123))).to_model()
    with KernelServer(model=base_model, registry=registry8) as server:
        with pytest.raises(ValueError, match="featurizer"):
            server.publish("alien", other)
