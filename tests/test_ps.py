"""Parameter-server client API tests (repro/ps: DESIGN.md section 8).

Covers the Glint-style surface -- factory, handles, pull futures, push
routes -- plus the two cross-cutting guarantees the redesign rests on:

  * **route invariance**: every ``PushRoute`` produces bitwise-identical
    matrices for the same reassignment batch (integer addition underneath);
  * **backend parity**: the same client script on ``InProcessBackend``
    and ``SpmdBackend`` (under forced host devices) produces bitwise-
    identical matrices, for each route.

Also the regression test for the padding-row invariant: coordinate pushes
with logical ids >= num_rows (fixed-buffer padding, or ids that would
*alias real rows* under the cyclic physical map) must be no-ops.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ps
from repro.core.pserver import CyclicLayout

ROUTES = [
    ps.DenseRoute(),
    ps.CooRoute(),
    ps.CooRoute(use_kernel=True),
    ps.HybridRoute(hot_words=7),
    ps.HybridRoute(hot_words=7, use_kernel=True),
]


def _reassign(v, k, n, seed, rows=None):
    rng = np.random.default_rng(seed)
    w = rng.integers(0, v, size=n).astype(np.int32)
    z0 = rng.integers(0, k, size=n).astype(np.int32)
    z1 = rng.integers(0, k, size=n).astype(np.int32)
    changed = rng.random(n) < 0.7
    w = jnp.asarray(w)
    return ps.Reassign(rows=w if rows is None else jnp.asarray(rows),
                       words=w, z_old=jnp.asarray(z0),
                       z_new=jnp.asarray(z1), changed=jnp.asarray(changed))


def _oracle_delta(re, v, k):
    """Dense reference: what any route must add to the matrix."""
    d = np.zeros((v, k), np.int64)
    rows = np.asarray(re.rows)
    zo, zn, ch = np.asarray(re.z_old), np.asarray(re.z_new), np.asarray(
        re.changed)
    np.add.at(d, (rows[ch], zo[ch]), -1)
    np.add.at(d, (rows[ch], zn[ch]), 1)
    return d


class TestFactoryAndHandles:
    def test_matrix_factory_roundtrip(self):
        client = ps.PSClient.create(num_shards=3)
        dense = jnp.arange(35, dtype=jnp.int32).reshape(7, 5)
        h = client.matrix_from_dense(dense)
        assert isinstance(h, ps.MatrixHandle)
        assert h.num_rows == 7 and h.cols == 5 and h.num_shards == 3
        np.testing.assert_array_equal(np.asarray(h.to_dense()),
                                      np.asarray(dense))

    def test_zeros_and_vector(self):
        client = ps.PSClient.create(num_shards=2)
        m = client.matrix(6, 4)
        assert int(m.to_dense().sum()) == 0
        vec = client.vector(5)
        vec = vec.push(jnp.array([1, 1, 3]), jnp.array([2, 1, 7]))
        np.testing.assert_array_equal(np.asarray(vec.value),
                                      [0, 3, 0, 7, 0])

    def test_pull_returns_future(self):
        client = ps.PSClient.create(num_shards=2)
        dense = jnp.arange(24, dtype=jnp.int32).reshape(8, 3)
        h = client.matrix_from_dense(dense)
        fut = h.pull(jnp.array([0, 7, 3]))
        assert isinstance(fut, ps.PullHandle)
        np.testing.assert_array_equal(np.asarray(fut.result()),
                                      np.asarray(dense)[[0, 7, 3]])
        # wait() is the Glint-named alias
        assert fut.wait() is fut.result()

    def test_pull_block_future_rides_scan_carry(self):
        """A PullHandle is a pytree: an in-flight pull can be carried
        across scan iterations -- the executor's double buffer."""
        client = ps.PSClient.create(num_shards=2)
        h = client.matrix_from_dense(
            jnp.arange(32, dtype=jnp.int32).reshape(8, 4))
        rpb = 4

        def body(carry, b):
            fut = carry
            rows = fut.result()
            nxt = h.pull_block((b + 1) % 2, rpb)
            return nxt, rows.sum()

        _, sums = jax.lax.scan(body, h.pull_block(0, rpb), jnp.arange(2))
        total = int(sums.sum())
        assert total == int(h.value.sum())

    def test_handle_is_jit_and_scan_compatible(self):
        client = ps.PSClient.create(num_shards=2)
        h = client.matrix(10, 4)

        @jax.jit
        def steps(h):
            def body(h, _):
                re = _reassign(10, 4, 16, 0)
                return h.push(re), ()
            h, _ = jax.lax.scan(body, h, jnp.arange(3))
            return h

        out = steps(h)
        want = _oracle_delta(_reassign(10, 4, 16, 0), 10, 4) * 3
        np.testing.assert_array_equal(np.asarray(out.to_dense()), want)


class TestRouteInvariance:
    """Paper section 3.3: the hybrid split is a traffic policy, not a
    semantic one -- every route yields identical matrices."""

    @pytest.mark.parametrize("use_kernels", [False, True])
    def test_all_routes_identical(self, use_kernels):
        v, k = 23, 8
        client = ps.PSClient.create(num_shards=3)
        base = jax.random.randint(jax.random.PRNGKey(0), (v, k), 0, 50)
        re = _reassign(v, k, 64, seed=1)
        want = np.asarray(base) + _oracle_delta(re, v, k)
        for route in ROUTES:
            h = client.matrix_from_dense(base, route=route)
            out = h.push(re, use_kernels=use_kernels)
            np.testing.assert_array_equal(
                np.asarray(out.to_dense()), want,
                err_msg=f"route {route!r} kernels={use_kernels}")

    def test_plan_traffic_shapes(self):
        """Routes differ in *what travels*, which plan() exposes."""
        v, k = 20, 6
        re = _reassign(v, k, 32, seed=3)
        dense_plan = ps.DenseRoute().plan(re, v, k)
        assert dense_plan.dense is not None and dense_plan.coo is None
        coo_plan = ps.CooRoute().plan(re, v, k)
        assert coo_plan.dense is None and coo_plan.coo is not None
        hyb = ps.HybridRoute(hot_words=5).plan(re, v, k)
        assert hyb.dense is not None and hyb.coo is not None
        # cold coordinates never name hot rows (with nonzero values)
        rows, _, vals = hyb.coo
        hot_hit = (np.asarray(rows) < 5) & (np.asarray(vals) != 0)
        assert not hot_hit.any()

    def test_route_for_mapping(self):
        assert isinstance(ps.route_for(None, 100), ps.DenseRoute)
        assert isinstance(ps.route_for(100, 100), ps.DenseRoute)
        assert isinstance(ps.route_for(0, 100), ps.CooRoute)
        r = ps.route_for(10, 100)
        assert isinstance(r, ps.HybridRoute) and r.hot_words == 10


class TestHotWordBoundaries:
    """Regression (ISSUE): ``HybridRoute.traffic()`` used to clamp
    ``hot_words`` to ``[0, num_rows]`` while ``plan()`` branched on the
    raw value -- the cost model and the executed plan could disagree at
    the edges.  One hoisted clamp (``HybridRoute.clamped``) now feeds
    both; every boundary value must produce the oracle delta through
    ``MatrixHandle.push`` and a traffic dict consistent with the plan."""

    V, K, B = 11, 5, 48

    @pytest.mark.parametrize("hot", [-1, 0, 1, 10, 11, 12])
    def test_boundary_push_matches_oracle(self, hot):
        client = ps.PSClient.create(num_shards=3)
        base = jax.random.randint(jax.random.PRNGKey(4), (self.V, self.K),
                                  0, 40)
        re = _reassign(self.V, self.K, self.B, seed=7)
        want = np.asarray(base) + _oracle_delta(re, self.V, self.K)
        route = ps.HybridRoute(hot_words=hot)
        out = client.matrix_from_dense(base, route=route).push(re)
        np.testing.assert_array_equal(np.asarray(out.to_dense()), want,
                                      err_msg=f"hot_words={hot}")

    @pytest.mark.parametrize("hot", [-1, 0, 1, 10, 11, 12])
    def test_traffic_agrees_with_plan(self, hot):
        """The clamp is hoisted: whatever traffic() says travels is what
        plan() materialises (dense row count and COO capacity)."""
        route = ps.HybridRoute(hot_words=hot)
        re = _reassign(self.V, self.K, self.B, seed=8)
        t = route.traffic(self.B, self.V, self.K)
        plan = route.plan(re, self.V, self.K, prefix_rows=True)
        dense_rows = 0 if plan.dense is None else plan.dense.shape[0]
        coo_cap = 0 if plan.coo is None else plan.coo[0].shape[0]
        assert t["dense_rows"] == dense_rows, f"hot_words={hot}"
        assert t["coo_cap"] == coo_cap, f"hot_words={hot}"

    @pytest.mark.parametrize("hot", [-1, 0, 1, 10, 11, 12])
    def test_partitioned_push_matches_oracle(self, hot):
        """Same boundaries through the pre-partitioned fast path."""
        client = ps.PSClient.create(num_shards=3)
        base = jax.random.randint(jax.random.PRNGKey(5), (self.V, self.K),
                                  0, 40)
        re = _reassign(self.V, self.K, self.B, seed=9)
        want = np.asarray(base) + _oracle_delta(re, self.V, self.K)
        route = ps.HybridRoute(hot_words=hot)
        clamped = route.clamped(self.V)
        re_p, hp = ps.partition_reassign(re, clamped)
        out = client.matrix_from_dense(base, route=route).push(
            re_p, hot_prefix=hp)
        np.testing.assert_array_equal(np.asarray(out.to_dense()), want,
                                      err_msg=f"hot_words={hot}")


class TestPrefixDelta:
    """The prefix-shaped ``RouteDelta`` wire format (the root fix for the
    hybrid regression): the hot dense block travels as [H, K], never
    padded to [V, K], and the partitioned cold buffer is sized to the
    post-split tail."""

    def test_hybrid_plan_dense_is_prefix_shaped(self):
        v, k, hot = 40, 6, 9
        re = _reassign(v, k, 32, seed=11)
        plan = ps.HybridRoute(hot_words=hot).plan(re, v, k,
                                                  prefix_rows=True)
        assert plan.dense.shape == (hot, k)

    def test_partitioned_cold_capacity_is_tail_sized(self):
        v, k, b, hot = 40, 6, 32, 9
        re = _reassign(v, k, b, seed=12)
        re_p, hp = ps.partition_reassign(re, hot)
        plan = ps.HybridRoute(hot_words=hot).plan(re_p, v, k,
                                                  prefix_rows=True,
                                                  hot_prefix=hp)
        assert plan.dense.shape == (hot, k)
        if hp == b:
            assert plan.coo is None
        else:
            assert plan.coo[0].shape[0] == 2 * (b - hp)
        # and the traffic dict says the same
        t = ps.HybridRoute(hot_words=hot).traffic(b, v, k, hot_prefix=hp)
        assert t["coo_cap"] == (0 if plan.coo is None
                                else plan.coo[0].shape[0])

    def test_block_delta_pads_back_to_full_width(self):
        v, k, hot = 25, 4, 6
        re = _reassign(v, k, 40, seed=13)
        route = ps.HybridRoute(hot_words=hot)
        full = np.asarray(route.block_delta(re, v, k, prefix_rows=True))
        assert full.shape == (v, k)
        np.testing.assert_array_equal(full, _oracle_delta(re, v, k))

    def test_push_prefix_applies_to_leading_rows(self):
        client = ps.PSClient.create(num_shards=3)
        h = client.matrix_from_dense(jnp.zeros((10, 4), jnp.int32))
        d = jnp.arange(12, dtype=jnp.int32).reshape(3, 4)
        out = np.asarray(h.push_prefix(d).to_dense())
        want = np.zeros((10, 4), np.int64)
        want[:3] = np.asarray(d)
        np.testing.assert_array_equal(out, want)

    def test_understated_hot_prefix_is_still_exact(self):
        """A *smaller* hot_prefix than the true hot count is legal: the
        surplus hot tokens just ride the COO path (the trust contract
        only forbids overstating)."""
        v, k, b, hot = 30, 5, 24, 8
        client = ps.PSClient.create(num_shards=2)
        re = _reassign(v, k, b, seed=14)
        re_p, hp = ps.partition_reassign(re, hot)
        want = _oracle_delta(re, v, k)
        route = ps.HybridRoute(hot_words=hot)
        for hp_use in {0, hp // 2, hp}:
            out = client.matrix(v, k).with_route(route).push(
                re_p, hot_prefix=hp_use)
            np.testing.assert_array_equal(np.asarray(out.to_dense()), want,
                                          err_msg=f"hot_prefix={hp_use}")


class TestRouteInvarianceRandom:
    """Property-style sweep: on random batches every route (and the
    partitioned hybrid) lands bitwise on the oracle.  Runs seeded cases
    always; widens via hypothesis when it is installed."""

    def _check(self, v, k, b, hot, seed):
        re = _reassign(v, k, b, seed=seed)
        want = _oracle_delta(re, v, k)
        client = ps.PSClient.create(num_shards=3)
        for route in (ps.DenseRoute(), ps.CooRoute(),
                      ps.HybridRoute(hot_words=hot)):
            out = client.matrix(v, k).with_route(route).push(re)
            np.testing.assert_array_equal(
                np.asarray(out.to_dense()), want,
                err_msg=f"route {route!r} v={v} k={k} b={b} seed={seed}")
        route = ps.HybridRoute(hot_words=hot)
        re_p, hp = ps.partition_reassign(re, route.clamped(v))
        out = client.matrix(v, k).with_route(route).push(re_p,
                                                         hot_prefix=hp)
        np.testing.assert_array_equal(
            np.asarray(out.to_dense()), want,
            err_msg=f"partitioned hybrid v={v} k={k} b={b} hot={hot}")

    @pytest.mark.parametrize("seed", range(6))
    def test_fixed_seeds(self, seed):
        rng = np.random.default_rng(1000 + seed)
        v = int(rng.integers(3, 60))
        k = int(rng.integers(2, 17))
        b = int(rng.integers(1, 96))
        hot = int(rng.integers(-2, v + 3))
        self._check(v, k, b, hot, seed)

    def test_hypothesis_widening(self):
        pytest.importorskip("hypothesis", reason="hypothesis not installed")
        from hypothesis import given, settings, strategies as st

        @given(st.integers(3, 60), st.integers(2, 17), st.integers(1, 96),
               st.integers(-2, 70), st.integers(0, 10_000))
        @settings(max_examples=25, deadline=None)
        def run(v, k, b, hot, seed):
            self._check(v, k, b, min(hot, v + 2), seed)

        run()


class TestSnapshotSweepCarry:
    """``snapshot_sweep`` carries its n_wk aggregate flat when the plan
    has an XLA-scattered cold tail, as ``[V, K]`` rows otherwise
    (``async_exec.nwk_carry_layout``).  The layout is a relayout, never a
    change of values."""

    V, K = 300, 100          # K is a multiple of no tile

    @pytest.mark.parametrize("staleness", [0, 2])
    @pytest.mark.parametrize("route,use_kernels,layout", [
        (ps.DenseRoute(), False, "rows"),
        (ps.CooRoute(), False, "flat"),
        (ps.HybridRoute(hot_words=1), False, "flat"),
        (ps.HybridRoute(hot_words=V // 2), False, "flat"),
        (ps.HybridRoute(hot_words=V // 2, use_kernel=False), True, "flat"),
    ], ids=["dense", "coo", "hybrid-1", "hybrid-half", "hybrid-kernels"])
    def test_matches_sweep_oracle(self, lda_state, route, use_kernels,
                                  layout, staleness):
        from repro.core import lightlda as lda
        from repro.train import async_exec

        corp, cfg, state = lda_state(seed=9, vocab=self.V, k=self.K,
                                     use_kernels=use_kernels)
        assert async_exec.nwk_carry_layout(route, cfg.V, cfg.K,
                                           use_kernels) == layout
        key = jax.random.PRNGKey(23)
        want = jax.jit(lambda s, k: lda.sweep(
            s, k, cfg, staleness=staleness))(state, key)
        got = jax.jit(lambda s, k: async_exec.snapshot_sweep(
            s, k, cfg, staleness=staleness, route=route))(state, key)
        np.testing.assert_array_equal(np.asarray(got.z), np.asarray(want.z))
        np.testing.assert_array_equal(np.asarray(got.nwk.value),
                                      np.asarray(want.nwk.value))
        np.testing.assert_array_equal(np.asarray(got.nk.value),
                                      np.asarray(want.nk.value))
        np.testing.assert_array_equal(np.asarray(got.ndk),
                                      np.asarray(want.ndk))
        # and the counts are the histograms of the new assignments
        nwk, nk, ndk = lda.rebuild_counts(got.w, got.d, got.z, got.valid,
                                          got.ndk.shape[0], cfg)
        np.testing.assert_array_equal(np.asarray(nwk.value),
                                      np.asarray(got.nwk.value))
        assert int(got.nk.value.sum()) == corp.num_tokens

    @pytest.mark.parametrize("route,v,k,use_kernels,layout", [
        (ps.CooRoute(), 300, 100, False, "flat"),
        (ps.HybridRoute(hot_words=0), 300, 100, False, "flat"),
        (ps.HybridRoute(hot_words=1), 300, 100, False, "flat"),
        (ps.HybridRoute(hot_words=2000, use_kernel=False), 102_660, 1024,
         True, "flat"),
        # the paper's 10x topics still fits a flat int32 index
        (ps.HybridRoute(hot_words=2000, use_kernel=False), 102_660, 10_240,
         True, "flat"),
        (ps.HybridRoute(hot_words=2000, use_kernel=False), 2 ** 21 - 1,
         1024, False, "flat"),
        (ps.HybridRoute(hot_words=2000, use_kernel=False), 2 ** 21, 1024,
         False, "rows"),
        (ps.CooRoute(), 2 ** 16, 2 ** 15, False, "rows"),
        (ps.DenseRoute(), 300, 100, False, "rows"),
        (ps.DenseRoute(), 300, 100, True, "rows"),
        (ps.HybridRoute(hot_words=300), 300, 100, False, "rows"),
        (ps.HybridRoute(hot_words=10_000), 300, 100, False, "rows"),
        (ps.CooRoute(), 300, 100, True, "rows"),
        (ps.CooRoute(use_kernel=True), 300, 100, False, "rows"),
        (ps.HybridRoute(hot_words=1), 300, 100, True, "rows"),
    ])
    def test_layout_follows_the_plan(self, route, v, k, use_kernels,
                                     layout):
        from repro.train import async_exec
        assert async_exec.nwk_carry_layout(route, v, k,
                                           use_kernels) == layout


class TestPushCooPaddingInvariant:
    """Regression: raw ``DistributedMatrix.push_sparse`` trusts its row
    ids; the client layer must mask padded logical ids >= num_rows, which
    otherwise either dirty padding rows or -- for ids >= pad_rows --
    *alias a real row* under the cyclic physical map."""

    def test_out_of_range_rows_are_noops(self):
        client = ps.PSClient.create(num_shards=3)
        h = client.matrix_from_dense(jnp.ones((7, 4), jnp.int32))
        lay = h.layout
        # id in [num_rows, pad_rows): a padding row; id >= pad_rows: would
        # alias a real row (to_physical is only injective below pad_rows)
        alias_id = lay.pad_rows + 1
        victim = int(lay.to_logical(lay.to_physical(alias_id) %
                                    lay.pad_rows))
        rows = jnp.array([7, alias_id, 2], jnp.int32)
        cols = jnp.array([1, 2, 3], jnp.int32)
        vals = jnp.array([5, 5, 1], jnp.int32)
        out = h.push_coo(rows, cols, vals)
        want = np.ones((7, 4), np.int64)
        want[2, 3] += 1                      # the only in-range entry
        np.testing.assert_array_equal(np.asarray(out.to_dense()), want)
        assert int(out.to_dense()[victim].sum()) == want[victim].sum()
        # padding rows of the physical array stay zero
        phys = np.asarray(out.value)
        logical = np.asarray(lay.to_logical(np.arange(lay.pad_rows)))
        assert (phys[logical >= 7] == 0).all()

    def test_aliasing_would_corrupt_without_mask(self):
        """Documents WHY the mask exists: the raw storage primitive does
        alias out-of-range ids onto real rows."""
        lay = CyclicLayout(7, 3)
        alias_id = lay.pad_rows + 1
        phys_a = int(lay.to_physical(alias_id))
        assert phys_a < lay.pad_rows  # lands inside the physical array...
        owner = int(lay.to_logical(phys_a))
        assert owner != alias_id      # ...on a row it does not own

    def test_read_only_view_rejects_push(self):
        client = ps.PSClient.create()
        view = client.matrix(4, 3).read_view()
        with pytest.raises(TypeError):
            view.push(None)
        with pytest.raises(TypeError):
            view.push_coo(None, None, None)
        assert view.to_dense().shape == (4, 3)


class TestInterpretDefault:
    def test_env_var_controls_default(self, monkeypatch):
        from repro.kernels import ops
        monkeypatch.setenv("REPRO_INTERPRET", "0")
        assert ops.default_interpret() is False
        monkeypatch.setenv("REPRO_INTERPRET", "1")
        assert ops.default_interpret() is True
        monkeypatch.delenv("REPRO_INTERPRET")
        # unset: CPU hosts interpret (this suite runs on CPU)
        if jax.default_backend() == "cpu":
            assert ops.default_interpret() is True

    def test_interpret_env_is_an_error_on_accelerators(self, monkeypatch):
        """An accelerator never falls back to the Pallas interpreter."""
        from repro.kernels import ops
        monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
        monkeypatch.delenv("REPRO_INTERPRET", raising=False)
        assert ops.default_interpret() is False
        monkeypatch.setenv("REPRO_INTERPRET", "0")
        assert ops.default_interpret() is False
        monkeypatch.setenv("REPRO_INTERPRET", "1")
        with pytest.raises(RuntimeError, match="REPRO_INTERPRET"):
            ops.default_interpret()

    def test_kernel_calls_resolve_none(self):
        """interpret=None flows end-to-end (would raise inside pallas if
        unresolved)."""
        from repro.kernels import ops
        re = _reassign(16, 8, 32, seed=5)
        d = ops.delta_push(re.rows, re.z_old, re.z_new,
                           re.changed, 16, 8, interpret=None)
        np.testing.assert_array_equal(np.asarray(d),
                                      _oracle_delta(re, 16, 8))


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs >= 2 devices (run tier-1 under "
                           "XLA_FLAGS=--xla_force_host_platform_device_"
                           "count=4 to exercise)")
class TestBackendParity:
    """The same PSClient script on InProcessBackend and SpmdBackend must
    produce bitwise-identical matrices, for each PushRoute."""

    def _script(self, client, base, batches, use_kernels=False):
        """The backend-agnostic client script: adopt counts, push every
        batch, read the result back."""
        h = client.matrix_from_dense(base, route=self.route)
        for re in batches:
            h = h.push(re, use_kernels=use_kernels)
        return h

    @pytest.mark.parametrize("route", ROUTES)
    def test_spmd_matches_in_process(self, route):
        from repro.sharding.mesh import make_mesh
        from jax.sharding import PartitionSpec as P

        self.route = route
        v, k = 19, 6
        n_dev = jax.device_count()
        base = jax.random.randint(jax.random.PRNGKey(2), (v, k), 0, 30)
        batches = [_reassign(v, k, 24, seed=10 + i) for i in range(n_dev)]

        # --- in-process: one worker pushes every batch ---
        host = self._script(ps.PSClient.create(num_shards=2), base, batches)
        want = np.asarray(host.to_dense())

        # --- SPMD: each worker pushes its own batch, psum merges ---
        mesh = make_mesh((n_dev,), ("x",))
        client = ps.PSClient.create(num_shards=2, axis_name="x")

        def worker(base_rep, re):
            re = jax.tree.map(lambda a: a[0], re)
            h = self._script(client, base_rep, [re])
            # each worker pushed only its delta; the psum inside push()
            # already merged all workers, so every replica holds the total
            return h.to_dense()

        stacked = jax.tree.map(
            lambda *xs: jnp.stack(xs), *batches)
        fn = jax.shard_map(worker, mesh=mesh,
                       in_specs=(P(), P("x", None)), out_specs=P(),
                       check_vma=False)
        got = np.asarray(fn(base, stacked))
        np.testing.assert_array_equal(got, want,
                                      err_msg=f"route {route!r}")

    def test_spmd_partitioned_hybrid_matches_in_process(self):
        """The prefix-delta SPMD path: each worker pushes its own
        pre-partitioned batch with a (common, understated-safe)
        hot_prefix; the prefix dense psums, the COO buffers all-gather,
        and every replica lands on the in-process result bitwise."""
        from repro.sharding.mesh import make_mesh
        from jax.sharding import PartitionSpec as P

        v, k, hot = 19, 6, 5
        n_dev = jax.device_count()
        route = ps.HybridRoute(hot_words=hot)
        base = jax.random.randint(jax.random.PRNGKey(3), (v, k), 0, 30)
        batches = [_reassign(v, k, 24, seed=40 + i) for i in range(n_dev)]
        parts = [ps.partition_reassign(re, hot) for re in batches]
        # shard_map runs ONE program, so the static hot_prefix must be
        # uniform: the min over workers is always safe (surplus hot
        # tokens ride the COO path, see TestPrefixDelta)
        hp = min(p[1] for p in parts)

        want = None
        h0 = ps.PSClient.create(num_shards=2).matrix_from_dense(
            base, route=route)
        for re_p, _ in parts:
            h0 = h0.push(re_p, hot_prefix=hp)
        want = np.asarray(h0.to_dense())

        mesh = make_mesh((n_dev,), ("x",))
        client = ps.PSClient.create(num_shards=2, axis_name="x")

        def worker(base_rep, re):
            re = jax.tree.map(lambda a: a[0], re)
            h = client.matrix_from_dense(base_rep, route=route)
            return h.push(re, hot_prefix=hp).to_dense()

        stacked = jax.tree.map(
            lambda *xs: jnp.stack(xs), *[p[0] for p in parts])
        fn = jax.shard_map(worker, mesh=mesh,
                       in_specs=(P(), P("x", None)), out_specs=P(),
                       check_vma=False)
        got = np.asarray(fn(base, stacked))
        np.testing.assert_array_equal(got, want)

    def test_model_sharded_pull_all(self):
        """pull_all on a model-sharded handle all-gathers the cyclic rows
        back into the full dense matrix on every worker."""
        from repro.sharding.mesh import make_mesh
        from jax.sharding import PartitionSpec as P

        shards = 2
        v, k = 10, 4
        dense = jnp.arange(v * k, dtype=jnp.int32).reshape(v, k)
        mesh = make_mesh((shards,), ("model",))
        full = ps.PSClient.create(num_shards=shards).matrix_from_dense(
            dense)
        client = ps.PSClient.create(num_shards=shards, model_axis="model")

        def worker(phys_local):
            h = client.wrap_matrix(phys_local, v)
            return h.pull_all().result()

        fn = jax.shard_map(worker, mesh=mesh, in_specs=(P("model", None),),
                       out_specs=P(), check_vma=False)
        got = np.asarray(fn(full.value))
        np.testing.assert_array_equal(got, np.asarray(dense))


class TestBackendProtocol:
    """``Backend`` is a runtime-checkable Protocol (DESIGN.md sec. 8):
    every substrate -- in-process, SPMD, tiered -- must satisfy it
    structurally, and single-process backends must realise each moment
    as the identity (the semantics the handles rely on outside
    ``shard_map``)."""

    ALL = [ps.InProcessBackend(), ps.SpmdBackend(),
           ps.SpmdBackend(axis_name="data", model_axis="model"),
           ps.TieredBackend(), ps.NetBackend()]

    @pytest.mark.parametrize("backend", ALL,
                             ids=lambda b: type(b).__name__)
    def test_structural_conformance(self, backend):
        assert isinstance(backend, ps.Backend)
        assert hasattr(backend, "axis_name")
        assert hasattr(backend, "model_axis")

    def test_non_backends_rejected(self):
        class Half:
            axis_name = model_axis = None

            def pull_full(self, s):
                return s

        assert not isinstance(object(), ps.Backend)
        assert not isinstance(Half(), ps.Backend)

    @pytest.mark.parametrize(
        "backend",
        [ps.InProcessBackend(), ps.SpmdBackend(), ps.TieredBackend(),
         ps.NetBackend()],
        ids=lambda b: type(b).__name__)
    def test_single_process_moments_are_identity(self, backend):
        """Outside collectives every moment is the identity: pulls see
        the stored matrix, reduces pass deltas through unchanged."""
        dense = jnp.arange(20, dtype=jnp.int32).reshape(5, 4)
        storage = ps.PSClient.create(num_shards=1).matrix_from_dense(
            dense).storage
        assert backend.pull_full(storage) is storage
        assert backend.localize(storage) is storage
        delta = jnp.ones((5, 4), jnp.int32)
        assert backend.reduce(delta) is delta
        assert backend.gather_concat(delta) is delta


class TestNetBackendConformance:
    """Route invariance over the wire (DESIGN.md sec. 15): whatever
    ``PushRoute`` plans, shipping the plan's dense/COO halves through a
    loopback ``PSServer`` must land bitwise identically to applying the
    same plan through ``InProcessBackend`` handles -- both sides are the
    same integer adds, one applied locally, one under the server lock."""

    V, K = 64, 8

    @pytest.fixture()
    def loopback(self):
        from repro.ps.net import NetClient, PSServer

        srv = PSServer(self.V, self.K).start()
        net = NetClient.connect(srv.address, name="conformance")
        yield net
        net.close()
        srv.stop()

    def test_connected_backend_is_a_backend(self, loopback):
        from repro.ps.net import NetBackend

        b = NetBackend(loopback)
        assert isinstance(b, ps.Backend)

    def test_connected_pull_full_refreshes_from_server(self, loopback):
        from repro.ps.net import NetBackend, wire

        dense = np.arange(self.V * self.K, dtype=np.int32).reshape(
            self.V, self.K)
        loopback.push_dense_prefix(wire.MAT_NWK, dense)
        stale = ps.PSClient.create(num_shards=1).matrix_from_dense(
            jnp.zeros((self.V, self.K), jnp.int32)).storage
        got = NetBackend(loopback).pull_full(stale)
        np.testing.assert_array_equal(np.asarray(got.to_dense()), dense)

    @pytest.mark.parametrize("route", [
        ps.DenseRoute(), ps.CooRoute(),
        ps.HybridRoute(hot_words=8)], ids=lambda r: r.label)
    def test_route_invariance_vs_in_process(self, loopback, route):
        from repro.ps.net import NetMatrixHandle, wire

        rng = np.random.default_rng(3)
        dense = rng.integers(1, 9, size=(self.V, self.K)).astype(np.int32)
        loopback.push_dense_prefix(wire.MAT_NWK, dense)
        local = ps.PSClient.create(num_shards=1).matrix_from_dense(
            jnp.asarray(dense), route=route)
        remote = NetMatrixHandle(loopback, self.V, self.K, route=route)

        re = _reassign(self.V, self.K, 160, seed=11)
        local = local.push(re)
        remote.push(re)
        np.testing.assert_array_equal(
            loopback.pull_full(wire.MAT_NWK),
            np.asarray(local.to_dense()))

    def test_vector_handle_matches_in_process(self, loopback):
        from repro.ps.net import NetVectorHandle, wire

        nk0 = np.arange(self.K, dtype=np.int32) * 3
        loopback.push_dense_prefix(wire.MAT_NK, nk0)
        local = ps.PSClient.create(num_shards=1).wrap_vector(
            jnp.asarray(nk0))
        remote = NetVectorHandle(loopback, self.K)
        delta = np.array([1, -1, 0, 2, 0, 0, -2, 0], np.int32)
        local = local.push_dense(jnp.asarray(delta))
        remote.push_dense(delta)
        np.testing.assert_array_equal(loopback.pull_full(wire.MAT_NK),
                                      np.asarray(local.value))
