"""Glint-style parameter-server client API (paper section 2).

This module is the **only** sanctioned way the rest of the codebase
touches parameters.  It mirrors Glint's client surface on JAX:

  * ``PSClient`` is the factory -- ``client.matrix(rows, cols)`` /
    ``client.vector(n)`` return handles, exactly like Glint's
    ``client.matrix[Double](rows, cols)`` returning a ``BigMatrix``;
  * ``MatrixHandle.pull(...)`` / ``pull_block(...)`` / ``pull_all()``
    return ``PullHandle`` *futures*: the read is issued immediately (JAX
    dispatch is asynchronous, so the transfer is in flight the moment the
    handle exists) and ``result()`` awaits it.  Issue -> overlap -> await
    is therefore a first-class primitive -- the pipelined executor's
    double-buffered prefetch is ``h = handle.pull_block(b + 1); ...;
    rows = h.result()``, no hand-rolled carry threading;
  * ``MatrixHandle.push(reassign)`` routes the update through the
    handle's declarative ``PushRoute`` (repro/ps/routes.py) and the
    client's ``Backend`` (repro/ps/backend.py): route decides the traffic
    shape (dense / coordinate / hybrid), backend supplies the collectives
    (identity in-process, ``psum``/``all_gather`` under SPMD).

Handles are registered pytrees whose array storage is the leaf and whose
client/route are static metadata, so they travel through ``jit`` /
``scan`` carries / ``shard_map`` unchanged.  The storage layer underneath
remains ``core/pserver.py``'s ``DistributedMatrix`` / ``DistributedVector``
(row-cyclic layout, paper section 2.2); constructing those directly
outside ``repro/ps`` is deprecated and gated in CI.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro import obs as _obs
from repro.core.pserver import DistributedMatrix, DistributedVector
from repro.ps.backend import Backend, InProcessBackend, SpmdBackend
from repro.ps.routes import DenseRoute, PushRoute, Reassign, RouteDelta

#: The backend names ``PSClient.create(backend=...)`` accepts.
BACKEND_NAMES = ("in_process", "spmd", "tiered", "net")


class BackendConfigError(ValueError):
    """An unknown or mis-configured ``backend=`` selection.

    Carries ``.valid`` -- the legal names -- so callers (and the error
    message itself) can list the choices instead of guessing.
    """

    def __init__(self, msg: str, valid: Tuple[str, ...] = BACKEND_NAMES):
        super().__init__(f"{msg}; valid backends: {', '.join(valid)}")
        self.valid = tuple(valid)


@jax.tree_util.register_pytree_node_class
class PullHandle:
    """Future for an issued pull (Glint's asynchronous read, section 2.3).

    JAX dispatch is asynchronous: the gather/slice behind this handle is
    already in flight (or, under ``jit``, schedulable by XLA wherever it
    overlaps best) when the handle is constructed.  ``result()`` awaits
    the value.  Registered as a pytree so an in-flight pull can ride a
    ``scan`` carry across loop iterations -- the executor's double buffer.
    """

    def __init__(self, value: jax.Array):
        self._value = value

    def result(self) -> jax.Array:
        """Await and return the pulled rows."""
        return self._value

    # Glint naming; identical semantics.
    wait = result

    def tree_flatten(self):
        return (self._value,), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0])

    def __repr__(self):
        return f"PullHandle(shape={getattr(self._value, 'shape', None)})"


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class MatrixHandle:
    """Client handle for one distributed matrix (Glint's ``BigMatrix``).

    ``storage`` is the row-cyclic physical matrix; ``client`` (backend,
    defaults) and ``route`` (push policy) are static metadata.  All reads
    return ``PullHandle`` futures; all writes return a new handle
    (functional updates -- the in-process analogue of an acknowledged
    push).
    """

    storage: DistributedMatrix
    client: "PSClient"
    route: PushRoute

    # --- pytree plumbing (client/route are static) ---
    def tree_flatten(self):
        return (self.storage,), (self.client, self.route)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux[0], aux[1])

    # --- storage mirror ---------------------------------------------------
    @property
    def value(self) -> jax.Array:
        """Physical (cyclic-ordered) array, [pad_rows, cols]."""
        return self.storage.value

    @property
    def num_rows(self) -> int:
        return self.storage.num_rows

    @property
    def num_shards(self) -> int:
        return self.storage.num_shards

    @property
    def cols(self) -> int:
        return self.storage.cols

    @property
    def layout(self):
        return self.storage.layout

    def spec(self, axis):
        return self.storage.spec(axis)

    def to_dense(self) -> jax.Array:
        return self.storage.to_dense()

    def num_blocks(self, rows_per_block: int) -> int:
        return self.storage.num_blocks(rows_per_block)

    def block_logical_rows(self, block, rows_per_block: int) -> jax.Array:
        return self.storage.block_logical_rows(block, rows_per_block)

    def with_value(self, value: jax.Array) -> "MatrixHandle":
        """Same handle over replaced physical storage (client/route kept)."""
        return dataclasses.replace(
            self, storage=dataclasses.replace(self.storage, value=value))

    def with_route(self, route: PushRoute) -> "MatrixHandle":
        return dataclasses.replace(self, route=route)

    # --- pulls (all asynchronous: they return futures) --------------------
    def pull(self, rows: jax.Array) -> PullHandle:
        """Pull logical rows (idempotent read, paper section 2.3)."""
        return PullHandle(self.storage.pull(rows))

    def pull_block(self, block, rows_per_block: int) -> PullHandle:
        """Pull a contiguous physical block -- the pipelined executor's
        prefetch unit (paper section 3.4)."""
        return PullHandle(self.storage.pull_block(block, rows_per_block))

    def pull_all(self) -> PullHandle:
        """Pull the full dense logical matrix (the snapshot pull; under
        ``SpmdBackend`` this is the all-gather over the server axis)."""
        full = self.client.backend.pull_full(self.storage)
        return PullHandle(full.to_dense())

    # --- pushes -----------------------------------------------------------
    def push(self, re: Reassign, *, use_kernels: bool = False,
             interpret: Optional[bool] = None,
             hot_prefix: Optional[int] = None) -> "MatrixHandle":
        """Push a reassignment batch through the handle's ``PushRoute``.

        The route plans the traffic (dense / coordinate / hybrid) and the
        backend merges worker contributions exactly once: the dense part
        -- prefix-shaped for the hybrid, see ``RouteDelta`` -- reduces
        elementwise (identity in-process, ``psum`` under SPMD) and lands
        through ``push_prefix``; the coordinate part stays compressed --
        the paper's per-reassignment message -- and under SPMD the
        workers' buffers are all-gathered and each entry applied once
        (``Backend.gather_concat``).  Only a model-sharded backend
        (``model_axis`` set) still materialises the full dense delta: its
        ``push_dense`` write-back needs the whole physical width.

        ``hot_prefix`` asserts the batch was pre-partitioned at the hot
        boundary (``ps.partition_reassign``), shrinking the hybrid's cold
        buffer to the post-split tail.

        When an obs session is installed (and the call is NOT inside a
        jax trace -- jitted pushes are timed by their enclosing sweep
        span), the push records a ``ps.push`` span labelled with the
        route and its traffic shape, the per-route cost table the
        autotuner (``ps.autotune``) consumes.  The span only reads clocks
        and syncs the produced value, so pushed values are identical with
        tracing on or off.
        """
        sp = _obs.span("ps.push", cat="ps")
        if sp is not _obs.NULL_SPAN:
            batch = int(re.rows.shape[0])
            sp.set(route=self.route.label, batch=batch,
                   **self.route.traffic(batch, self.num_rows, self.cols,
                                        hot_prefix=hot_prefix))
        interpret = self.client.interpret if interpret is None else interpret
        backend = self.client.backend
        if backend.model_axis is not None:
            dense = self.route.block_delta(
                re, self.num_rows, self.cols, use_kernels=use_kernels,
                prefix_rows=True, interpret=interpret)
            out = self.push_dense(backend.reduce(dense))
        else:
            plan = self.route.plan(re, self.num_rows, self.cols,
                                   use_kernels=use_kernels, prefix_rows=True,
                                   hot_prefix=hot_prefix, interpret=interpret)
            if backend.axis_name is not None:
                plan = RouteDelta(
                    None if plan.dense is None else backend.reduce(plan.dense),
                    None if plan.coo is None else tuple(
                        backend.gather_concat(x) for x in plan.coo))
            out = self.push_plan(plan,
                                 use_kernel=self.route.coo_kernel(use_kernels),
                                 interpret=interpret)
        if sp is not _obs.NULL_SPAN:
            sp.sync_on(out.value)
            ms = sp.end()
            reg = _obs.metrics_registry()
            if reg is not None:
                reg.histogram(f"ps.push_ms.{self.route.label}").record(ms)
                reg.counter(f"ps.push_count.{self.route.label}").inc()
        return out

    def push_plan(self, plan: "RouteDelta", *, use_kernel: bool = False,
                  interpret: Optional[bool] = None) -> "MatrixHandle":
        """Apply an already-planned ``RouteDelta`` (the server-side half
        of a push): prefix-dense block through ``push_prefix``, coordinate
        entries through ``push_coo``.  ``MatrixHandle.push`` is plan +
        merge + this; benchmarks time the two halves separately because
        the paper's worker builds the plan *while sampling* (the split
        cost is amortised into the sweep), so the server apply is the
        contended-resource cost."""
        out = self
        if plan.dense is not None:
            out = out.push_prefix(plan.dense)
        if plan.coo is not None:
            rows, cols, vals = plan.coo
            out = out.push_coo(rows, cols, vals, use_kernel=use_kernel,
                               interpret=interpret)
        return out

    def push_dense(self, delta_dense: jax.Array) -> "MatrixHandle":
        """Push a dense logical [num_rows, cols] delta."""
        return dataclasses.replace(
            self, storage=self.storage.push_dense(delta_dense))

    def push_prefix(self, delta: jax.Array) -> "MatrixHandle":
        """Push a dense delta covering the first ``delta.shape[0]``
        logical rows (the hybrid's hot-word buffer wire format)."""
        return dataclasses.replace(
            self, storage=self.storage.push_prefix(delta))

    def push_rows(self, rows: jax.Array, deltas: jax.Array) -> "MatrixHandle":
        """Push row deltas to logical rows (duplicates accumulate)."""
        return dataclasses.replace(self,
                                   storage=self.storage.push(rows, deltas))

    def push_coo(self, rows: jax.Array, cols: jax.Array, vals: jax.Array, *,
                 use_kernel: bool = False,
                 interpret: Optional[bool] = None) -> "MatrixHandle":
        """Push compressed ``(row, col, +/-value)`` coordinate deltas.

        Guards the storage layer's padding-row invariant: logical row ids
        ``>= num_rows`` (fixed-size buffers padded with arbitrary ids, or
        ids beyond ``pad_rows`` that would *alias a real row* under the
        cyclic physical map) are masked to value-0 no-ops here, in the
        client, so ``DistributedMatrix.push_sparse`` only ever sees
        in-range traffic.
        """
        interpret = self.client.interpret if interpret is None else interpret
        vals = jnp.where(rows < self.num_rows, vals, 0)
        rows = jnp.where(rows < self.num_rows, rows, 0)
        return dataclasses.replace(
            self, storage=self.storage.push_sparse(
                rows, cols, vals, use_kernel=use_kernel,
                interpret=interpret))

    def store_block(self, block, rows: jax.Array,
                    rows_per_block: int) -> "MatrixHandle":
        """Write back a physical block previously pulled by its exclusive
        owner (``rows`` replaces the block).  This is the pipelined
        executor's group-boundary merge: legal because blocks own disjoint
        physical rows, so pulled-rows + local-delta *is* the push."""
        new = jax.lax.dynamic_update_slice_in_dim(
            self.storage.value, rows, block * rows_per_block, axis=0)
        return self.with_value(new)

    def push_block(self, block, delta_rows: jax.Array,
                   rows_per_block: int) -> "MatrixHandle":
        """Additive push of a [rows_per_block, cols] delta to one physical
        block (pull + add + store; prefer ``store_block`` when the pulled
        rows are already in hand)."""
        cur = self.storage.pull_block(block, rows_per_block)
        return self.store_block(block, cur + delta_rows.astype(cur.dtype),
                                rows_per_block)

    # --- backend moments --------------------------------------------------
    def localize(self) -> "MatrixHandle":
        """Keep only this server shard's rows (SPMD write-back)."""
        return dataclasses.replace(
            self, storage=self.client.backend.localize(self.storage))

    # --- serving ----------------------------------------------------------
    def read_view(self) -> "ReadOnlyView":
        """Read-only snapshot view of this handle (serving side)."""
        return ReadOnlyView(self)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class VectorHandle:
    """Client handle for one distributed vector (Glint's ``BigVector``).

    For LDA this holds ``n_k`` -- tiny and read by every sampling step, so
    the natural placement is replicated and pushes reduce over workers."""

    storage: DistributedVector
    client: "PSClient"

    def tree_flatten(self):
        return (self.storage,), (self.client,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux[0])

    @property
    def value(self) -> jax.Array:
        return self.storage.value

    def with_value(self, value: jax.Array) -> "VectorHandle":
        return dataclasses.replace(self, storage=DistributedVector(value))

    def pull(self, idx: jax.Array) -> PullHandle:
        return PullHandle(self.storage.pull(idx))

    def pull_all(self) -> PullHandle:
        return PullHandle(self.storage.value)

    def push(self, idx: jax.Array, deltas: jax.Array) -> "VectorHandle":
        return dataclasses.replace(self, storage=self.storage.push(idx,
                                                                   deltas))

    def push_dense(self, delta: jax.Array) -> "VectorHandle":
        """Push a dense delta, reduced exactly once over workers."""
        delta = self.client.backend.reduce(delta)
        return dataclasses.replace(self,
                                   storage=self.storage.push_dense(delta))


@dataclasses.dataclass(frozen=True)
class ReadOnlyView:
    """Read-only snapshot view of a ``MatrixHandle`` (DESIGN.md sec. 3).

    The serving-side face of a handle: pulls only.  The snapshot
    publisher freezes one of these per published version; any attempt to
    push through a view is a programming error and raises."""

    handle: MatrixHandle

    @property
    def num_rows(self) -> int:
        return self.handle.num_rows

    @property
    def cols(self) -> int:
        return self.handle.cols

    def pull(self, rows: jax.Array) -> PullHandle:
        return self.handle.pull(rows)

    def pull_block(self, block, rows_per_block: int) -> PullHandle:
        return self.handle.pull_block(block, rows_per_block)

    def to_dense(self) -> jax.Array:
        return self.handle.pull_all().result()

    def push(self, *a, **k):
        raise TypeError("ReadOnlyView is read-only: serving snapshots "
                        "never push (publish from the training handle)")

    push_dense = push_coo = store_block = push_rows = push


@dataclasses.dataclass(frozen=True)
class PSClient:
    """The parameter-server client factory (Glint's ``Client``).

    ``backend`` supplies the collectives (``InProcessBackend`` /
    ``SpmdBackend``); ``interpret`` is the client-level Pallas-interpret
    default threaded to every kernel call issued through handles (None:
    resolved by ``kernels.ops.default_interpret`` -- interpret on the CPU,
    compiled on a TPU).
    """

    backend: Backend = InProcessBackend()
    num_shards: int = 1
    interpret: Optional[bool] = None

    @classmethod
    def create(cls, num_shards: int = 1, *, backend=None, server=None,
               mesh=None, axis_name=None,
               model_axis: Optional[str] = None,
               interpret: Optional[bool] = None) -> "PSClient":
        """Build a client.

        ``backend`` selects by name (``BACKEND_NAMES``: ``"in_process"``,
        ``"spmd"``, ``"tiered"``, ``"net"``) or takes a ``Backend``
        instance directly; an unknown name raises ``BackendConfigError``
        listing the choices.  ``backend=None`` keeps the historical
        inference: no mesh/axes means ``InProcessBackend`` (single
        device), any of ``mesh`` / ``axis_name`` / ``model_axis`` means
        ``SpmdBackend`` for use under ``shard_map`` -- ``axis_name``
        defaults to all of the mesh's axes (every shard is a worker),
        ``model_axis`` names the server axis holding the cyclic ``n_wk``
        rows.  ``backend="net"`` with ``server="host:port"`` connects a
        ``NetClient`` to a running ``repro.launch.ps_server``; without
        ``server`` the net backend is detached (structural use only).
        """
        if isinstance(backend, str):
            backend = cls._backend_by_name(backend, server=server,
                                           mesh=mesh, axis_name=axis_name,
                                           model_axis=model_axis)
        elif backend is None:
            if mesh is None and axis_name is None and model_axis is None:
                backend = InProcessBackend()
            else:
                backend = cls._spmd_backend(mesh, axis_name, model_axis)
        elif not isinstance(backend, Backend):
            raise BackendConfigError(
                f"backend must be a name or a ps.Backend instance "
                f"(got {type(backend).__name__})")
        return cls(backend=backend, num_shards=num_shards,
                   interpret=interpret)

    @staticmethod
    def _spmd_backend(mesh, axis_name, model_axis) -> SpmdBackend:
        if axis_name is None and mesh is not None:
            axis_name = tuple(mesh.axis_names)
        if isinstance(axis_name, list):
            axis_name = tuple(axis_name)
        return SpmdBackend(axis_name=axis_name, model_axis=model_axis)

    @classmethod
    def _backend_by_name(cls, name: str, *, server, mesh, axis_name,
                         model_axis) -> Backend:
        if name == "in_process":
            return InProcessBackend()
        if name == "spmd":
            if mesh is None and axis_name is None:
                raise BackendConfigError(
                    "backend='spmd' needs mesh= or axis_name= (the "
                    "shard_map axes the collectives run over)")
            return cls._spmd_backend(mesh, axis_name, model_axis)
        if name == "tiered":
            from repro.ps.tiered import TieredBackend
            return TieredBackend()
        if name == "net":
            from repro.ps.net import NetBackend, NetClient
            net = NetClient.connect(server) if server else None
            return NetBackend(net=net)
        raise BackendConfigError(f"unknown backend {name!r}")

    def with_backend(self, backend: Backend) -> "PSClient":
        return dataclasses.replace(self, backend=backend)

    # --- matrix factories (the only sanctioned construction points) ------
    def matrix(self, rows: int, cols: int, dtype=jnp.int32, *,
               route: PushRoute = DenseRoute()) -> MatrixHandle:
        """Allocate a zeroed [rows, cols] distributed matrix."""
        return MatrixHandle(
            DistributedMatrix.zeros(rows, cols, self.num_shards, dtype),
            self, route)

    def matrix_from_dense(self, dense: jax.Array, *,
                          route: PushRoute = DenseRoute()) -> MatrixHandle:
        """Wrap a dense logical matrix (rows scattered cyclically)."""
        return MatrixHandle(
            DistributedMatrix.from_dense(dense, self.num_shards), self,
            route)

    def wrap_matrix(self, value: Union[jax.Array, DistributedMatrix],
                    num_rows: Optional[int] = None, *,
                    route: PushRoute = DenseRoute()) -> MatrixHandle:
        """Adopt existing physical (cyclic-ordered) storage into a handle.

        ``value`` is either a ``DistributedMatrix`` or a raw physical
        array (then ``num_rows`` is required) -- the bridge for storage
        arriving from a ``shard_map`` boundary or a checkpoint.
        """
        if isinstance(value, DistributedMatrix):
            storage = value
        else:
            assert num_rows is not None, "num_rows required for raw arrays"
            storage = DistributedMatrix(value, num_rows, self.num_shards)
        return MatrixHandle(storage, self, route)

    def tiered_matrix_from_dense(self, dense: jax.Array, hot_rows: int,
                                 path: str, *,
                                 route: PushRoute = DenseRoute()):
        """Wrap a dense logical matrix in tiered storage: the full table
        lands in a host memmap cold store at ``path`` and the top
        ``hot_rows`` rows are promoted into a device hot tier
        (``repro.ps.tiered``).  Single-shard only -- the tiered store is
        the in-process scale-up axis, the SPMD backend the scale-out one.
        """
        from repro.ps.tiered import tiered_matrix_from_dense
        assert self.num_shards == 1, "tiered storage is single-shard"
        return tiered_matrix_from_dense(dense, hot_rows, path, route=route,
                                        client=self)

    # --- vector factories -------------------------------------------------
    def vector(self, n: int, dtype=jnp.int32) -> VectorHandle:
        return VectorHandle(DistributedVector.zeros(n, dtype), self)

    def wrap_vector(self, value: Union[jax.Array, DistributedVector]
                    ) -> VectorHandle:
        if not isinstance(value, DistributedVector):
            value = DistributedVector(value)
        return VectorHandle(value, self)


def client_for(cfg, *, mesh=None, axis_name=None,
               model_axis: Optional[str] = None) -> PSClient:
    """Client matching an ``LDAConfig`` (shard count + interpret default)."""
    return PSClient.create(num_shards=cfg.num_shards, mesh=mesh,
                           axis_name=axis_name, model_axis=model_axis,
                           interpret=cfg.kernel_interpret)
