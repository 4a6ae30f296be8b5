"""Compile-cache serving tests: content-addressed hits/misses, LRU
eviction, thread safety, input validation, the NetServer's stacked
multi-net dispatch (ISSUE 2 acceptance: 4 versions in one jitted call,
bit-exact vs serving each CompiledNet individually), the
mesh-sharded stacked dispatch (ISSUE 4: shard_map over the slot
dimension when a mesh with a data axis is active, single-device
fallback otherwise), and multi-round calls served in launches of 2^k
whole slot rounds."""
import os
import subprocess
import sys
import threading

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import quantize
from repro import netgen
from repro.netgen import telemetry
from repro.netgen.serve import _pass_fingerprint
from repro.serve.engine import pad_slots

from _netgen_helpers import images, random_net

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(__file__)), "benchmarks"))
from check_trace import check_launches, check_rounds, check_spans  # noqa: E402


def _random_net(seed: int, sizes=(12, 9, 4), lo=-5, hi=5):
    return random_net(seed, sizes, lo=lo, hi=hi)


def _images(seed: int, b: int, n_in: int) -> np.ndarray:
    return images(seed, b, n_in, salt=77)


def _ref(net, x):
    return np.asarray(quantize.predict_quantized(net)(jnp.asarray(x)))


# ---------------------------------------------------------------------------
# Digest
# ---------------------------------------------------------------------------

def test_digest_content_addressed():
    net = _random_net(0)
    clone = quantize.QuantizedNet(weights=[w.copy() for w in net.weights])
    assert net.digest() == clone.digest()
    # dtype of the container must not matter, only the integer content
    as_i8 = quantize.QuantizedNet(
        weights=[w.astype(np.int8) for w in net.weights])
    assert as_i8.digest() == net.digest()
    # any perturbation must change it
    w = [w.copy() for w in net.weights]
    w[0][0, 0] += 1
    assert quantize.QuantizedNet(weights=w).digest() != net.digest()
    other_thr = quantize.QuantizedNet(
        weights=list(net.weights), input_threshold=64)
    assert other_thr.digest() != net.digest()


def test_digest_rejects_float_weights():
    with pytest.raises(TypeError):
        quantize.weights_digest([np.ones((2, 2), np.float32)])


# ---------------------------------------------------------------------------
# Cache hit/miss semantics
# ---------------------------------------------------------------------------

def test_cache_hit_returns_same_object():
    cache = netgen.CompileCache()
    net = _random_net(1)
    clone = quantize.QuantizedNet(weights=[w.copy() for w in net.weights])
    first = cache.get_or_compile(net)
    again = cache.get_or_compile(clone)      # equal content, new containers
    assert again is first
    st = cache.stats()
    assert (st.hits, st.misses) == (1, 1)
    assert st.compile_seconds > 0
    key = cache.key_for(net)
    assert key in cache and cache.compile_seconds(key) > 0


def test_cache_misses_on_weights_passes_backend():
    cache = netgen.CompileCache()
    net = _random_net(2)
    base = cache.get_or_compile(net)

    perturbed = [w.copy() for w in net.weights]
    perturbed[1][0, 0] -= 1
    assert cache.get_or_compile(
        quantize.QuantizedNet(weights=perturbed)) is not base
    assert cache.get_or_compile(
        net, passes=(netgen.delete_zero_terms,)) is not base
    assert cache.get_or_compile(net, backend="pallas") is not base
    st = cache.stats()
    assert (st.hits, st.misses) == (0, 4)


def test_cache_key_distinguishes_backend_opts_and_partial_passes():
    import functools
    cache = netgen.CompileCache()
    net = _random_net(3)
    k_plain = cache.key_for(net, backend="verilog")
    k_named = cache.key_for(net, backend="verilog", module_name="other")
    assert k_plain != k_named
    budget = functools.partial(netgen.share_common_addends, max_new_nodes=2)
    assert _pass_fingerprint(budget) != _pass_fingerprint(
        netgen.share_common_addends)
    assert cache.key_for(net, passes=(budget,)) != cache.key_for(
        net, passes=(netgen.share_common_addends,))


def test_cache_refuses_unfingerprintable_passes():
    """A lambda/closure pass has no stable fingerprint — two different
    ones would alias to one key and serve each other's artifacts."""
    cache = netgen.CompileCache()
    net = _random_net(8)
    with pytest.raises(ValueError, match="lambda"):
        cache.key_for(net, passes=(lambda c: c,))

    def make(budget):
        def p(c):
            return netgen.share_common_addends(c, max_new_nodes=budget)
        return p

    with pytest.raises(ValueError, match="functools.partial"):
        cache.key_for(net, passes=(make(1),))


def test_cache_eviction_bound():
    cache = netgen.CompileCache(capacity=2)
    nets = [_random_net(10 + i) for i in range(3)]
    first = cache.get_or_compile(nets[0])
    cache.get_or_compile(nets[1])
    cache.get_or_compile(nets[2])            # evicts nets[0] (LRU)
    assert len(cache) == 2
    assert cache.stats().evictions == 1
    assert cache.key_for(nets[0]) not in cache
    assert cache.get_or_compile(nets[0]) is not first   # recompiled
    assert cache.stats().misses == 4
    with pytest.raises(ValueError):
        netgen.CompileCache(capacity=0)


def test_cache_lru_recency():
    cache = netgen.CompileCache(capacity=2)
    a, b, c = (_random_net(20 + i) for i in range(3))
    ca = cache.get_or_compile(a)
    cache.get_or_compile(b)
    cache.get_or_compile(a)                  # touch a: b is now LRU
    cache.get_or_compile(c)                  # evicts b, keeps a
    assert cache.get_or_compile(a) is ca
    assert cache.stats().evictions == 1


def test_cache_thread_safety_smoke():
    cache = netgen.CompileCache()
    net = _random_net(4)
    results = [None] * 8
    barrier = threading.Barrier(len(results))

    def worker(i):
        barrier.wait()
        results[i] = cache.get_or_compile(net)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(results))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(r is results[0] for r in results)
    st = cache.stats()
    assert st.misses == 1 and st.hits == len(results) - 1


def test_cached_compile_net_uses_default_cache():
    net = _random_net(5, sizes=(7, 5, 3))
    a = netgen.cached_compile_net(net)
    b = netgen.cached_compile_net(net)
    assert a is b


# ---------------------------------------------------------------------------
# CompiledNet input validation
# ---------------------------------------------------------------------------

def test_compiled_net_rejects_bad_input():
    net = _random_net(6)
    compiled = netgen.compile_net(net)
    x = _images(6, 8, 12)
    ok = np.asarray(compiled(x))
    assert ok.shape == (8,)
    np.testing.assert_array_equal(np.asarray(compiled(jnp.asarray(x))), ok)
    with pytest.raises(TypeError, match="uint8"):
        compiled(x.astype(np.float32))
    with pytest.raises(TypeError, match="uint8"):
        compiled(x.astype(np.int32))
    with pytest.raises(ValueError, match=r"\(batch, 12\)"):
        compiled(x[:, :5])                   # wrong trailing dim
    with pytest.raises(ValueError, match=r"\(batch, 12\)"):
        compiled(x[0])                       # 1-D
    with pytest.raises(TypeError):
        compiled(x.tolist())                 # no dtype at all


def test_verilog_artifact_not_callable():
    compiled = netgen.compile_net(_random_net(7), backend="verilog")
    with pytest.raises(TypeError, match="not callable"):
        compiled(_images(7, 4, 12))


# ---------------------------------------------------------------------------
# NetServer: routing, slot batching, stacked dispatch
# ---------------------------------------------------------------------------

def test_netserver_routes_per_version():
    server = netgen.NetServer(slot_capacity=16)
    nets = {f"v{i}": _random_net(30 + i) for i in range(2)}
    for name, net in nets.items():
        server.register(name, net)
    assert server.versions() == ["v0", "v1"]
    x = _images(30, 10, 12)
    for name, net in nets.items():
        np.testing.assert_array_equal(server.predict(name, x), _ref(net, x))
    assert server.dispatch_counts["single"] == 2
    with pytest.raises(KeyError):
        server.predict("nope", x)


def test_netserver_slot_chunking():
    """Batches beyond slot capacity are served in fixed-shape chunks."""
    server = netgen.NetServer(slot_capacity=8)
    net = _random_net(31)
    server.register("v", net)
    x = _images(31, 21, 12)                  # 3 chunks: 8 + 8 + 5
    np.testing.assert_array_equal(server.predict("v", x), _ref(net, x))
    assert server.predict("v", x[:0]).shape == (0,)


def test_netserver_stacked_dispatch_4_versions_bit_exact():
    """ISSUE acceptance: 4 model versions through ONE jitted multi-net
    call, per-version outputs bit-exact vs each CompiledNet individually."""
    cache = netgen.CompileCache()
    server = netgen.NetServer(cache=cache, slot_capacity=16)
    nets = {f"v{i}": _random_net(40 + i) for i in range(4)}
    for name, net in nets.items():
        server.register(name, net)
    reqs = {name: _images(40 + i, 12, 12) for i, name in enumerate(nets)}
    out = server.predict_many(reqs)
    assert server.dispatch_counts["stacked"] == 1
    assert server.dispatch_counts["fallback"] == 0
    for name, net in nets.items():
        individual = np.asarray(server.compiled_for(name)(
            pad_slots(reqs[name], 16)[0]))[:reqs[name].shape[0]]
        np.testing.assert_array_equal(out[name], individual, err_msg=name)
        np.testing.assert_array_equal(out[name], _ref(net, reqs[name]))


def test_netserver_stacked_pads_pruned_hidden_widths():
    """Versions whose pruning left different hidden widths still stack:
    the padded columns are constant-0 units (exact under strict step)."""
    a = _random_net(50)
    wz = [w.copy() for w in _random_net(51).weights]
    wz[0][:, :4] = 0                         # 4 dead hidden units
    b = quantize.QuantizedNet(weights=wz)
    ca = netgen.compile_net(a)
    cb = netgen.compile_net(b)
    assert (netgen.as_layered_weights(ca.circuit)[0].shape[1]
            != netgen.as_layered_weights(cb.circuit)[0].shape[1])
    server = netgen.NetServer(slot_capacity=8)
    server.register("a", a)
    server.register("b", b)
    x = _images(50, 8, 12)
    out = server.predict_many({"a": x, "b": x})
    assert server.dispatch_counts["stacked"] == 1
    np.testing.assert_array_equal(out["a"], _ref(a, x))
    np.testing.assert_array_equal(out["b"], _ref(b, x))


def test_netserver_stacked_chunks_unequal_batches():
    server = netgen.NetServer(slot_capacity=8)
    nets = {name: _random_net(60 + i) for i, name in enumerate("ab")}
    for name, net in nets.items():
        server.register(name, net)
    reqs = {"a": _images(60, 19, 12), "b": _images(61, 3, 12)}
    out = server.predict_many(reqs)
    for name, net in nets.items():
        np.testing.assert_array_equal(out[name], _ref(net, reqs[name]))


def test_netserver_pallas_stacked_dispatch():
    server = netgen.NetServer(
        backend="pallas", slot_capacity=8, warmup=False)
    nets = {name: _random_net(70 + i, sizes=(10, 8, 4))
            for i, name in enumerate("ab")}
    for name, net in nets.items():
        server.register(name, net)
    x = _images(70, 6, 10)
    out = server.predict_many({"a": x, "b": x})
    assert server.dispatch_counts["stacked"] == 1
    for name, net in nets.items():
        np.testing.assert_array_equal(out[name], _ref(net, x), err_msg=name)


def test_netserver_reregister_invalidates_stacked_dispatch():
    """Re-registering a version must drop the stacked dispatch built for
    the old weights — serving stale predictions silently is the failure
    the generation counter guards against."""
    server = netgen.NetServer(slot_capacity=8, warmup=False)
    old = _random_net(100)
    other = _random_net(101)
    server.register("a", old)
    server.register("b", other)
    x = _images(100, 8, 12)
    server.predict_many({"a": x, "b": x})            # builds the stacked fn
    new = _random_net(102)
    server.register("a", new)                        # same name, new weights
    out = server.predict_many({"a": x, "b": x})
    np.testing.assert_array_equal(out["a"], _ref(new, x))
    np.testing.assert_array_equal(out["b"], _ref(other, x))
    assert server.dispatch_counts["stacked"] == 2


def test_netserver_fallback_on_incompatible_topologies():
    server = netgen.NetServer(slot_capacity=8)
    shallow = _random_net(80)                          # 12-9-4
    deep = _random_net(81, sizes=(12, 8, 8, 4))        # different depth
    server.register("s", shallow)
    server.register("d", deep)
    x = _images(80, 8, 12)
    out = server.predict_many({"s": x, "d": x})
    assert server.dispatch_counts["fallback"] == 1
    assert server.dispatch_counts["stacked"] == 0
    np.testing.assert_array_equal(out["s"], _ref(shallow, x))
    np.testing.assert_array_equal(out["d"], _ref(deep, x))


def test_netserver_stacked_build_error_reaches_caller(monkeypatch):
    """A kernel the compiler refuses (Pallas raises its TPU lowering
    refusals as ValueError) must fail the request, not turn into a
    per-version fallback that hides the device path."""
    from repro.netgen import serve as serve_mod

    def refuse(*args, **kwargs):
        raise ValueError("block shape refused by the TPU lowering")

    server = netgen.NetServer(slot_capacity=8, warmup=False)
    server.register("a", _random_net(82))
    server.register("b", _random_net(83))
    monkeypatch.setattr(serve_mod, "compile_multi", refuse)
    x = _images(82, 8, 12)
    with pytest.raises(ValueError, match="refused by the TPU lowering"):
        server.predict_many({"a": x, "b": x})
    assert server.dispatch_counts["fallback"] == 0
    assert server.stack_report() == {}


def test_netserver_shares_cache_across_servers():
    """A second server over the same cache acquires predictors warm."""
    cache = netgen.CompileCache()
    net = _random_net(90)
    netgen.NetServer(cache=cache, slot_capacity=8).register("v", net)
    assert cache.stats().misses == 1
    netgen.NetServer(cache=cache, slot_capacity=8).register("v", net)
    st = cache.stats()
    assert (st.misses, st.hits) == (1, 1)


def test_netserver_rejects_bad_config():
    with pytest.raises(ValueError):
        netgen.NetServer(backend="verilog")
    with pytest.raises(ValueError):
        netgen.NetServer(slot_capacity=0)


def test_stack_layered_weights_incompatibility_errors():
    c = lambda seed, sizes: netgen.compile_net(  # noqa: E731
        _random_net(seed, sizes=sizes)).circuit
    with pytest.raises(ValueError, match="depth"):
        netgen.stack_layered_weights([c(0, (8, 6, 4)), c(1, (8, 6, 6, 4))])
    with pytest.raises(ValueError, match="input width"):
        netgen.stack_layered_weights([c(0, (8, 6, 4)), c(1, (9, 6, 4))])
    with pytest.raises(ValueError, match="class count"):
        netgen.stack_layered_weights([c(0, (8, 6, 4)), c(1, (8, 6, 5))])
    with pytest.raises(ValueError, match="no circuits"):
        netgen.stack_layered_weights([])


# ---------------------------------------------------------------------------
# Mesh-sharded stacked dispatch (ISSUE 4)
# ---------------------------------------------------------------------------

def test_netserver_sharded_stacked_under_mesh():
    """With a mesh carrying a data axis active, the stacked dispatch
    runs under shard_map (slot dimension split across the axis) and
    stays bit-exact; leaving the mesh context falls back to the
    single-device build."""
    import math

    import jax
    from repro.launch.mesh import make_host_mesh
    from repro.parallel import sharding as shd

    server = netgen.NetServer(slot_capacity=8, warmup=False)
    nets = {name: _random_net(110 + i) for i, name in enumerate("ab")}
    for name, net in nets.items():
        server.register(name, net)
    x = _images(110, 11, 12)                 # 2 slot rounds: 8 + 3

    plain = server.predict_many({"a": x, "b": x})
    assert server.dispatch_counts["sharded"] == 0
    # a data axis that divides slot_capacity, whatever the host has
    with shd.use_mesh(make_host_mesh(data=math.gcd(len(jax.devices()), 8))):
        sharded = server.predict_many({"a": x, "b": x})
    assert server.dispatch_counts["sharded"] == 1
    assert server.dispatch_counts["stacked"] == 2
    for name, net in nets.items():
        np.testing.assert_array_equal(sharded[name], plain[name])
        np.testing.assert_array_equal(sharded[name], _ref(net, x))
    # back outside the mesh: the single-device build serves again
    server.predict_many({"a": x, "b": x})
    assert server.dispatch_counts["sharded"] == 1


def test_netserver_sharded_falls_back_without_data_axis():
    """A mesh without a data axis (or a capacity the axis cannot divide)
    must cleanly fall back to the single-device stacked dispatch."""
    from repro.launch.mesh import make_mesh_compat
    from repro.parallel import sharding as shd

    server = netgen.NetServer(slot_capacity=8, warmup=False)
    for i, name in enumerate("ab"):
        server.register(name, _random_net(120 + i))
    x = _images(120, 8, 12)
    with shd.use_mesh(make_mesh_compat((1,), ("model",))):
        out = server.predict_many({"a": x, "b": x})
    assert server.dispatch_counts["stacked"] == 1
    assert server.dispatch_counts["sharded"] == 0
    np.testing.assert_array_equal(out["a"], _ref(_random_net(120), x))


_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
import sys
sys.path.insert(0, {test_dir!r})
from _netgen_helpers import random_net, images
from repro.core import quantize
from repro import netgen
from repro.launch.mesh import make_host_mesh
from repro.parallel import sharding as shd

assert len(jax.devices()) == 8
nets = {{name: random_net(130 + i, (12, 9, 4), lo=-5, hi=5)
        for i, name in enumerate("abc")}}
reqs = {{name: images(130 + i, 19, 12, salt=77)
        for i, name in enumerate("abc")}}
server = netgen.NetServer(target={target!r}, slot_capacity=16, warmup=False)
for name, net in nets.items():
    server.register(name, net)

single = server.predict_many(reqs)                   # single-device path
assert server.dispatch_counts["sharded"] == 0
rounds = server._slot_rounds.value
with shd.use_mesh(make_host_mesh(data=8)):           # 8-way batch sharding
    # 19 rows a version: one launch of 2 slot rounds, (3, 32, 12) over 8
    sharded = server.predict_many(reqs)
assert server.dispatch_counts["sharded"] == 1, server.dispatch_counts
assert server._slot_rounds.value - rounds == 2
for name, net in nets.items():
    ref = np.asarray(quantize.predict_quantized(net)(jnp.asarray(reqs[name])))
    assert np.array_equal(sharded[name], single[name]), name
    assert np.array_equal(sharded[name], ref), name
print("SHARDED_NETSERVE_OK")
"""


@pytest.mark.parametrize("target", ["jnp", "pallas[packed=true]"])
def test_netserver_sharded_8_devices_bit_exact(target):
    """ISSUE satellite: sharded-vs-single-device bit-exactness of
    stacked predict_many on a real (faked-8-device) mesh — subprocess,
    because device count is fixed at jax init."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = _SHARDED_SCRIPT.format(
        test_dir=os.path.dirname(os.path.abspath(__file__)), target=target)
    out = subprocess.run(
        [sys.executable, "-c", script],
        env={**os.environ, "PYTHONPATH": src},
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SHARDED_NETSERVE_OK" in out.stdout


def test_pad_slots():
    x = np.arange(6, dtype=np.uint8).reshape(3, 2)
    padded, n = pad_slots(x, 5)
    assert padded.shape == (5, 2) and n == 3
    np.testing.assert_array_equal(padded[:3], x)
    assert not padded[3:].any()
    same, n_same = pad_slots(x, 3)
    assert same is x and n_same == 3
    with pytest.raises(ValueError):
        pad_slots(x, 2)


# ---------------------------------------------------------------------------
# ISSUE 7 regression tests: the latent serving-path concurrency bugs
# ---------------------------------------------------------------------------

def test_cache_compile_does_not_block_unrelated_keys():
    """Head-of-line blocking regression: while key A sits in a slow
    compile, a hit on key B — and even a fresh compile of key C — must
    proceed (the old code held the cache lock across the compile)."""
    import time

    started, release = threading.Event(), threading.Event()

    def slow_compile(circuit, **opts):
        started.set()
        assert release.wait(10.0), "test never released the slow compile"
        return lambda x: np.zeros((np.asarray(x).shape[0],), np.int64)

    netgen.register_target(netgen.Target(
        name="slowfake_hol", kind="callable",
        description="test-only gated-slow compile", compile=slow_compile))
    cache = netgen.CompileCache()
    net_a, net_b, net_c = _random_net(80), _random_net(81), _random_net(82)
    warm_b = cache.get_or_compile(net_b)     # resident before the stall
    out: dict = {}
    slow = threading.Thread(target=lambda: out.update(
        a=cache.get_or_compile(net_a, backend="slowfake_hol")))
    slow.start()
    try:
        assert started.wait(10.0)
        # watchdog thread instead of a bare call: under the old locking
        # this blocked forever, which should fail the test, not hang it
        hit: dict = {}
        h = threading.Thread(target=lambda: hit.update(
            b=cache.get_or_compile(net_b)))
        h.start()
        h.join(5.0)
        assert hit.get("b") is warm_b, \
            "hit on unrelated key blocked behind an in-flight compile"
        miss: dict = {}
        c = threading.Thread(target=lambda: miss.update(
            c=cache.get_or_compile(net_c)))
        c.start()
        c.join(30.0)
        assert "c" in miss, \
            "compile of unrelated key blocked behind an in-flight compile"
    finally:
        release.set()
        slow.join(10.0)
    assert out["a"] is cache.get_or_compile(net_a, backend="slowfake_hol")
    st = cache.stats()
    assert st.misses == st.compiles == 3     # b, a, c: one compile each
    assert st.hits == 2                      # the gated hit + the re-get


def test_register_warms_up_before_publishing():
    """Warmup race regression: a registering version must not be visible
    to concurrent predicts until its warmup trace has executed (the old
    code published into the routing table first)."""
    import time

    calls: list = []
    gate = threading.Event()

    def compile_cold(circuit, **opts):
        def artifact(x):
            calls.append(np.asarray(x).shape)
            if len(calls) == 1:              # the warmup execution
                assert gate.wait(10.0), "test never released the warmup"
            return np.zeros((np.asarray(x).shape[0],), np.int64)
        return artifact

    netgen.register_target(netgen.Target(
        name="coldfake_pub", kind="callable",
        description="test-only gated warmup", compile=compile_cold))
    server = netgen.NetServer(target="coldfake_pub", slot_capacity=4,
                              warmup=True)
    reg = threading.Thread(
        target=lambda: server.register("v", _random_net(85)))
    reg.start()
    try:
        deadline = time.time() + 10.0
        while not calls and time.time() < deadline:
            time.sleep(0.005)
        assert calls, "warmup never ran"
        # mid-warmup, the second thread must still see the OLD state
        assert server.versions() == []
        with pytest.raises(KeyError):
            server.predict("v", _images(86, 2, 12))
    finally:
        gate.set()
        reg.join(10.0)
    assert server.versions() == ["v"]
    assert len(calls) == 1                   # exactly one warmup execution
    assert calls[0] == (4, 12)               # the serving slot shape
    server.predict("v", _images(86, 2, 12))
    assert len(calls) == 2


def test_predict_many_skewed_batches_skip_empty_rounds():
    """Skewed-batch regression: with batch sizes (1, 4*cap) the rounds
    after the first must serve ONLY the longer version — no all-zero
    padded block for the exhausted one — and occupancy is observed over
    requested slots only."""
    cap = 4
    server = netgen.NetServer(slot_capacity=cap)
    net_a, net_b = _random_net(87), _random_net(88)
    server.register("a", net_a)
    server.register("b", net_b)
    xa, xb = _images(89, 1, 12), _images(90, 4 * cap, 12)
    out = server.predict_many({"a": xa, "b": xb})
    np.testing.assert_array_equal(out["a"], _ref(net_a, xa))
    np.testing.assert_array_equal(out["b"], _ref(net_b, xb))
    h = netgen.telemetry.get_registry().histogram(
        "netgen_slot_occupancy", server=server._scope)
    # round 0 stacks both: (1 + 4) / (2 * 4); rounds 1-3 are b alone
    # through the single-version tail at full occupancy. The old code
    # padded a's empty row into every round: 4 observations over 8
    # slots each, summing to 2.125.
    assert h.count == 4
    assert abs(h.sum - (5 / 8 + 3 * 1.0)) < 1e-9, h.snapshot()
    assert server.dispatch_counts["stacked"] == 1


def test_predict_many_records_per_version_service_time():
    """Latency misattribution regression: a 1-row version co-batched
    with a 16*cap-row one must record only the rounds it participated
    in, not the whole-call wall clock — and every version gets exactly
    one latency observation per dispatch (the check_trace.py gate)."""
    cap = 4
    server = netgen.NetServer(slot_capacity=cap)
    net_s, net_b = _random_net(91), _random_net(92)
    server.register("small", net_s)
    server.register("big", net_b)
    reqs = {"small": _images(93, 1, 12), "big": _images(94, 16 * cap, 12)}
    out = server.predict_many(reqs)
    np.testing.assert_array_equal(out["small"], _ref(net_s, reqs["small"]))
    np.testing.assert_array_equal(out["big"], _ref(net_b, reqs["big"]))
    tel = netgen.telemetry.get_registry()
    for v in ("small", "big"):
        lat = tel.histogram("netgen_predict_latency_seconds",
                            server=server._scope, version=v)
        req = tel.counter("netgen_requests_total",
                          server=server._scope, version=v)
        assert lat.count == 1 and int(req.value) == 1
    small = tel.histogram("netgen_predict_latency_seconds",
                          server=server._scope, version="small")
    big = tel.histogram("netgen_predict_latency_seconds",
                        server=server._scope, version="big")
    # small saw round 0 only; big additionally paid 15 more rounds
    assert small.sum < big.sum


# ---------------------------------------------------------------------------
# Multi-round calls: launches of 2^k whole slot rounds, fetched once each
# ---------------------------------------------------------------------------

CAP = 4
# rows of a call -> the slot rounds of each launch that serves it; a call
# of at most CAP rows is the one (CAP, n_in) / (M, CAP, n_in) launch
LAUNCHES = {
    1: [1], CAP: [1], CAP + 1: [2], 3 * CAP + 5: [4, 1],
    32 * CAP: [16, 16], 33 * CAP: [16, 16, 1],
    100 * CAP + 1: [16] * 6 + [4, 1],
}
# rows per version -> (versions, slot rounds) of each launch: stacked
# while every version of the launch has rows, then the remaining subset
SKEWED = [
    ((1, 4 * CAP), [(2, 1), (1, 2), (1, 1)]),
    ((33 * CAP, 3 * CAP + 5), [(2, 4), (2, 1), (1, 16), (1, 8), (1, 4)]),
    ((100 * CAP + 1, 32 * CAP),
     [(2, 16)] * 2 + [(1, 16)] * 4 + [(1, 4), (1, 1)]),
    ((CAP + 1, 33 * CAP, 3 * CAP + 5),
     [(3, 2), (2, 2), (2, 1), (1, 16), (1, 8), (1, 4)]),
]


@pytest.fixture
def traced():
    """Span tracing on for one test, over zeroed metrics."""
    telemetry.reset()
    telemetry.enable()
    yield telemetry.get_registry()
    telemetry.disable()
    telemetry.reset()


def _launch_spy(monkeypatch) -> list:
    """The block shape of every predictor call, in order: single-version
    artifacts and stacked multi-net dispatches alike."""
    from repro.netgen import serve as serve_mod
    from repro.netgen.session import Artifact

    shapes = []
    call, build = Artifact.__call__, serve_mod.compile_multi

    def single(self, x):
        shapes.append(x.shape)
        return call(self, x)

    def multi(*args, **kwargs):
        fn = build(*args, **kwargs)

        def stacked(block):
            shapes.append(block.shape)
            return fn(block)
        return stacked

    monkeypatch.setattr(Artifact, "__call__", single)
    monkeypatch.setattr(serve_mod, "compile_multi", multi)
    return shapes


def _assert_launch_records(server, reg, rounds: list) -> list:
    """One `netgen.kernel` span per launch with its slot rounds in
    `rounds`, the rounds counted in `netgen_slot_rounds_total`, one
    occupancy observation per slot round, one latency observation per
    version, and the launch split that `check_trace` gates. Returns the
    finished spans as dicts."""
    spans = [r.as_dict() for r in reg.spans()]
    kernels = [r for r in spans if r["name"] == "netgen.kernel"]
    assert [r["attrs"]["rounds"] for r in kernels] == rounds
    assert reg.counter("netgen_slot_rounds_total",
                       server=server._scope).value == sum(rounds)
    assert reg.histogram("netgen_slot_occupancy",
                         server=server._scope).count == sum(rounds)
    for v in server.versions():
        assert reg.histogram("netgen_predict_latency_seconds",
                             server=server._scope, version=v).count == 1
    assert check_rounds(spans) == []
    assert check_spans(spans, require=("netgen.dispatch", "netgen.kernel",
                                       "netgen.round.stage")) == []
    return spans


@pytest.mark.parametrize("versions", [1, 2])
@pytest.mark.parametrize("n", sorted(LAUNCHES))
def test_multi_round_call_served_in_power_of_two_launches(
        monkeypatch, traced, n, versions):
    """A call of n rows per version is bit-exact and reaches the
    predictor as launches of 2^k whole slot rounds, largest first; a
    call of at most one slot round is the one launch it always was."""
    shapes = _launch_spy(monkeypatch)
    server = netgen.NetServer(slot_capacity=CAP, warmup=False)
    nets = {f"v{i}": _random_net(140 + i) for i in range(versions)}
    for name, net in nets.items():
        server.register(name, net)
    reqs = {name: _images(140 + n + i, n, 12)
            for i, name in enumerate(nets)}
    out = server.predict_many(reqs)
    for name, net in nets.items():
        np.testing.assert_array_equal(out[name], _ref(net, reqs[name]),
                                      err_msg=name)
    lead = () if versions == 1 else (versions,)
    assert shapes == [lead + (k * CAP, 12) for k in LAUNCHES[n]]
    _assert_launch_records(server, traced, LAUNCHES[n])
    path = "single" if versions == 1 else "stacked"
    assert server.dispatch_counts[path] == 1


@pytest.mark.parametrize("rows, launches", SKEWED,
                         ids=["x".join(map(str, r)) for r, _ in SKEWED])
def test_skewed_multi_round_call_launches_only_versions_with_rows(
        monkeypatch, traced, rows, launches):
    """Skewed calls stack whole rounds while every version of the launch
    has rows, then serve the rest through the remaining subset or the
    single-version tail; an exhausted version never rides a launch."""
    shapes = _launch_spy(monkeypatch)
    server = netgen.NetServer(slot_capacity=CAP, warmup=False)
    nets = {f"v{i}": _random_net(150 + i) for i in range(len(rows))}
    for name, net in nets.items():
        server.register(name, net)
    reqs = {name: _images(150 + i, b, 12)
            for i, (name, b) in enumerate(zip(nets, rows))}
    out = server.predict_many(reqs)
    for name, net in nets.items():
        np.testing.assert_array_equal(out[name], _ref(net, reqs[name]),
                                      err_msg=name)
    assert shapes == [((m,) if m > 1 else ()) + (k * CAP, 12)
                      for m, k in launches]
    _assert_launch_records(server, traced, [k for _, k in launches])
    assert server.dispatch_counts["stacked"] == 1


@pytest.mark.parametrize("versions", [1, 2])
def test_fusednet_multi_round_launch_passes_the_launch_gates(
        traced, versions):
    """On the megakernel a multi-round launch is still ONE pallas_call:
    `check_launches` holds, and the slot rounds over the kernel
    launches read the rounds per launch."""
    cap = 8
    server = netgen.NetServer(target="pallas[fusednet=true]",
                              slot_capacity=cap, warmup=False)
    nets = {f"v{i}": random_net(160 + i, (20, 13, 5), lo=-5, hi=5)
            for i in range(versions)}
    for name, net in nets.items():
        server.register(name, net)
    reqs = {name: images(160 + i, 3 * cap + 5, 20)   # 4 rounds: 1 launch
            for i, name in enumerate(nets)}
    launched = telemetry.kernel_launches("fusednet").value
    out = server.predict_many(reqs)
    for name, net in nets.items():
        np.testing.assert_array_equal(out[name], _ref(net, reqs[name]),
                                      err_msg=name)
    spans = _assert_launch_records(server, traced, [4])
    assert [r["attrs"]["form"] for r in spans
            if r["name"] == "netgen.kernel"] == ["fusednet"]
    launches = telemetry.kernel_launches("fusednet").value - launched
    assert launches == 1
    samples = [("netgen_kernel_launches_total", {"form": "fusednet"},
                float(telemetry.kernel_launches("fusednet").value))]
    assert check_launches(spans, samples) == []


# ---------------------------------------------------------------------------
# Two launches in flight: launch k+1 is dispatched before launch k is fetched
# ---------------------------------------------------------------------------

CNV_ROUND = 256 * 3072          # a 256-row slot round of 32x32x3 images
LFC_ROUND = 256 * 784           # of 28x28 images


@pytest.mark.parametrize("rounds, round_bytes, plan", [
    (32, CNV_ROUND, [8, 8, 8, 8]), (12, CNV_ROUND, [8, 4]),
    (9, CNV_ROUND, [8, 1]), (8, CNV_ROUND, [8]), (1, CNV_ROUND, [1]),
    (32, LFC_ROUND, [16, 16]), (12, LFC_ROUND, [8, 4]),
    # a stacked pair of 784-byte versions: 16 rounds would be 6.1 MiB
    (32, 2 * LFC_ROUND, [8, 8, 8, 8]),
    (40, 48, [16, 16, 8]),
    # a round above the byte cap is still served, one round a launch
    (3, 7 << 20, [1, 1, 1]),
])
def test_launch_rounds_are_greedy_powers_of_two_within_the_launch_caps(
        rounds, round_bytes, plan):
    from repro.netgen.serve import (MAX_LAUNCH_BYTES, MAX_ROUNDS_PER_LAUNCH,
                                    _launch_rounds)

    assert (MAX_ROUNDS_PER_LAUNCH, MAX_LAUNCH_BYTES) == (16, 6 << 20)
    assert _launch_rounds(rounds, round_bytes) == plan


def _ahead(server) -> int:
    return int(telemetry.get_registry().counter(
        "netgen_launches_ahead_total", server=server._scope).value)


def _tiny_convnet():
    from repro.core.convnet import ConvLayer, ConvNet, DenseLayer, PoolLayer

    rng = np.random.default_rng(170)

    def pm(shape):
        return np.where(rng.standard_normal(shape) >= 0, 1, -1)
    w1 = pm((3, 3, 3, 4))
    return ConvNet.from_bipolar((12, 12, 3), [
        ConvLayer(w1, 128 * w1.sum(axis=(0, 1, 2))
                  + rng.integers(-100, 100, 4)),
        ConvLayer(pm((3, 3, 4, 4)), rng.integers(-4, 5, 4)),
        PoolLayer(),
        ConvLayer(pm((3, 3, 4, 8)), rng.integers(-4, 5, 8)),
        DenseLayer(pm((2 * 2 * 8, 10)), np.zeros(10, int))])


# (nets, rows per version): 32 slot rounds of the longest version
THIRTY_TWO_ROUNDS = {
    "dense": ([_random_net(171)], [32 * CAP]),
    "stacked-exhausted": ([_random_net(172), _random_net(173)],
                          [32 * CAP, 20 * CAP + 3]),
    "convnet": ([_tiny_convnet], [32 * CAP - 1]),
}


@pytest.mark.parametrize("case", sorted(THIRTY_TWO_ROUNDS))
def test_thirty_two_round_call_two_in_flight_matches_graph_evaluate(
        monkeypatch, case):
    """A 32-round call on the megakernel (a dense net; a stacked pair of
    which one version runs out mid-call and the other finishes alone;
    a conv net), in launches of 8 rounds as CNV's rows get them, gives
    exactly `graph.evaluate`'s answers, row for row."""
    from repro.netgen import graph
    from repro.netgen import serve as serve_mod

    monkeypatch.setattr(serve_mod, "MAX_ROUNDS_PER_LAUNCH", 8)
    nets, rows = THIRTY_TWO_ROUNDS[case]
    server = netgen.NetServer(target="pallas[fusednet=true]",
                              slot_capacity=CAP, warmup=False)
    reqs = {}
    for i, (net, n) in enumerate(zip(nets, rows)):
        net = net() if callable(net) else net
        art = server.register(f"v{i}", net)
        reqs[f"v{i}"] = _images(171 + i, n, art.circuit.n_inputs)
    out = server.predict_many(reqs)
    for v, x in reqs.items():
        np.testing.assert_array_equal(
            out[v], graph.evaluate(server.compiled_for(v).circuit, x),
            err_msg=v)
    assert _ahead(server) >= 3


@pytest.mark.parametrize("rows, ahead", [
    (1, 0), (CAP, 0), (16 * CAP, 0), (16 * CAP + 1, 1), (32 * CAP, 1),
    (33 * CAP, 2), (64 * CAP, 3),
])
@pytest.mark.parametrize("versions", [1, 2])
def test_launches_ahead_counts_all_but_the_first_launch_of_a_call(
        rows, ahead, versions):
    """A call served as one unbroken run of n launches dispatches n - 1
    of them while an earlier one is not yet fetched."""
    server = netgen.NetServer(slot_capacity=CAP, warmup=False)
    reqs = {}
    for i in range(versions):
        server.register(f"v{i}", _random_net(180 + i))
        reqs[f"v{i}"] = _images(180 + i, rows, 12)
    server.predict_many(reqs)
    assert _ahead(server) == ahead
    server.predict_many(reqs)
    assert _ahead(server) == 2 * ahead


def test_engine_rounds_launch_nothing_ahead():
    with netgen.ServingEngine(target="jnp", slot_capacity=CAP,
                              max_batch_delay=0.001) as eng:
        eng.register("v", _random_net(185))
        futs = [eng.submit("v", x) for x in _images(185, 3 * CAP, 12)]
        [f.result(timeout=30) for f in futs]
        assert _ahead(eng.server) == 0


class _Pending:
    """A predictor's result that counts itself live until it is fetched:
    zeros of the block's shape less its last axis."""

    def __init__(self, block_shape: tuple, live: list, peak: list):
        self.shape = block_shape[:-1]
        self._live = live
        live.append(self)
        peak[0] = max(peak[0], len(live))

    def copy_to_host_async(self):
        pass

    def __array__(self, dtype=None, copy=None):
        self._live.remove(self)
        return np.zeros(self.shape, np.int64)


def test_at_most_two_launches_of_a_call_are_alive(monkeypatch):
    """Counted by a predictor stub: no launch is dispatched while two
    are unfetched, on the single-version and the stacked path."""
    from repro.netgen.session import Artifact

    live, peak = [], [0]

    def pending(*args):
        return _Pending(args[-1].shape, live, peak)
    server = netgen.NetServer(slot_capacity=CAP, warmup=False)
    for v in ("a", "b"):
        server.register(v, _random_net(190))
    monkeypatch.setattr(Artifact, "__call__", pending)
    monkeypatch.setattr(server, "_stacked_fn", lambda names: (pending, False))
    x = _images(190, 40 * CAP + 1, 12)
    assert server.predict("a", x).shape == (x.shape[0],)
    assert peak == [2] and live == []
    peak[0] = 0
    out = server.predict_many({"a": x, "b": x})
    assert out["a"].shape == out["b"].shape == (x.shape[0],)
    assert peak == [2] and live == []


@pytest.mark.parametrize("rows", [(33 * CAP + 3,),
                                  (33 * CAP + 3, 20 * CAP + 1)],
                         ids=["single", "stacked"])
def test_slot_counts_are_those_of_one_launch_of_the_same_rows(
        monkeypatch, rows):
    """`netgen_slot_rounds_total` and `netgen_slot_occupancy` read the
    same for a call split into launches of 16 rounds as for the same
    rows in one launch."""
    from repro.netgen import serve as serve_mod

    def counts(max_rounds):
        monkeypatch.setattr(serve_mod, "MAX_ROUNDS_PER_LAUNCH", max_rounds)
        server = netgen.NetServer(slot_capacity=CAP, warmup=False)
        reqs = {}
        for i, n in enumerate(rows):
            server.register(f"v{i}", _random_net(195 + i))
            reqs[f"v{i}"] = _images(195 + i, n, 12)
        server.predict_many(reqs)
        reg = telemetry.get_registry()
        occ = reg.histogram("netgen_slot_occupancy", server=server._scope)
        return (reg.counter("netgen_slot_rounds_total",
                            server=server._scope).value,
                occ.count, occ.sum, sorted(occ._values))

    assert counts(16) == counts(64)
