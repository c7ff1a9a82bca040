"""SPMD serving: shard_map the paged KV pool and the engine's jitted ticks
over a device mesh, keeping every host-side decision (admission, growth,
preemption, window-trim, chunk accounting) untouched.

Design: **exactness-first tensor parallelism**. The acceptance bar for the
sharded engine is that greedy outputs on an N-device mesh are *bit-identical*
to the 1-device engine (across fp and quantized pools, chunked prefill, GQA,
windows, and forced preemption), so the partitioning only ever splits
computations along *batch-like* dimensions — never along a floating-point
reduction:

  * q/k/v projections contract over the replicated ``embed`` dim and are
    sharded on their **output** head dims (``heads``/``kv_heads`` on the
    ``model`` axis, per ``distributed.sharding.CANDIDATES``): each device
    computes an identical slice of the identical full computation.
  * the paged-attention walk is fully parallel over heads: each device walks
    its local kv-head group of the pool (softmax and PV reductions run over
    page slots and head_dim, both unsharded), so the decode-dominant KV
    HBM traffic — the roofline term that sizes the pool — is truly divided
    by the ``model`` axis.
  * contraction-sharded matmuls (attn out-projection over ``heads``, FFN
    down-projection over ``d_ff``) would need a partial-sum all-reduce,
    which is NOT bit-stable; instead the *inputs* are all-gathered (pure
    data movement) and the contraction runs whole on every device, exactly
    as on one device. Weights stay sharded **at rest** (per-device HBM is
    what the admission roofline prices); they are gathered at use like FSDP.
  * everything else (embedding lookup, norms, residuals, sampling inputs)
    is replicated.

The ``data`` mesh axis is a pure at-rest FSDP axis for parameters (the
``embed``/FSDP candidates in ``CANDIDATES``); batch-sharding the decode tick
across ``data`` is the async-host-loop follow-on (ROADMAP).

The KV pool shards on ``kv_heads`` only. The ``cache_seq`` fall-through in
``CANDIDATES`` belongs to the *dense* ring/full-cache decode path (see
``make_ac``'s flash-decoding hints and the dry-run decode cells): splitting
page slots across devices would split the online-softmax reduction and break
bit-exactness, so the engine instead *requires* ``num_kv_heads %
mesh.shape["model"] == 0`` (``validate_mesh``) and keeps pages whole.

Pool / page-table layout per device (mesh ``model=N``)::

    pool["sub{j}"]["k"|"v"]         (G, num_pages, K/N, page, hd)
    quantized: {"q":   (G, num_pages, K/N, page, hd_store) int8,
                "scale":(G, num_pages, K/N, page) f32}     # scale tiles
    page_table, positions, tokens   replicated (host-built every tick)

Every device holds a 1/N kv-head slice of EVERY page, so one host-side page
allocation covers all shards and the allocator/scheduler/preemption logic is
unchanged — while per-device page bytes drop N×, which is exactly how
``derive_policy(mesh_model=N)`` finds ~N× the pool capacity in the same
per-device HBM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed import sharding as shlib
from repro.models import transformer

F32 = jnp.float32
MODEL_AXIS = "model"

# Leaves whose ``model``-axis sharding is an *output* dim of their matmul:
# they are used as local slices (never gathered on that dim). Everything
# else sharded on ``model`` — and every ``data``/FSDP-sharded dim — is
# all-gathered at use inside the shard_map body.
_LOCAL_KEYS = ("'wq'", "'wk'", "'wv'", "'w_in'", "'w_gate'")
_LOCAL_AXES = ("heads", "kv_heads", "d_ff")

# Full-layout prefill caches (G, B, Sp, K, hd): sharded on kv_heads only,
# like the pool itself (transformer.pool_axes explains why cache_seq's
# fall-through never applies to paged serving).
_CACHE_KV_AXES = ("layer", None, None, "kv_heads", "head_dim")


def _axes_tuple(a):
    return a if isinstance(a, tuple) else (a,)


def validate_mesh(cfg, mesh: Mesh) -> None:
    """The exactness contract the SPMD engine needs from (cfg, mesh)."""
    unknown = set(mesh.shape) - {"data", "model"}
    if unknown:
        raise ValueError(f"serving mesh axes must be data/model, "
                         f"got {sorted(mesh.shape)}")
    tp = mesh.shape.get(MODEL_AXIS, 1)
    if cfg.num_kv_heads % tp or cfg.num_heads % tp:
        raise ValueError(
            f"{cfg.name}: heads ({cfg.num_heads}) and kv heads "
            f"({cfg.num_kv_heads}) must divide the model axis ({tp}); the "
            f"paged walk shards on kv_heads only — page slots stay whole "
            f"so the online softmax keeps its 1-device reduction order")
    if cfg.family not in ("dense", "moe"):
        raise NotImplementedError(
            f"sharded engine serves dense/moe decoders; {cfg.name} "
            f"(family={cfg.family!r}) is an open item (ROADMAP)")


def partition_specs(abstract, logical, mesh: Mesh):
    """Pytree of full-rank PartitionSpecs via the divisibility-aware
    ``choose_spec`` rules (shard_map wants explicit trailing Nones)."""
    flat_a, tdef = jax.tree.flatten(abstract)
    flat_l = tdef.flatten_up_to(logical)
    out = []
    for a, l in zip(flat_a, flat_l):
        if l is None:
            l = (None,) * a.ndim
        sp = shlib.choose_spec(a.shape, l, mesh)
        out.append(P(*(tuple(sp) + (None,) * (a.ndim - len(sp)))))
    return jax.tree.unflatten(tdef, out)


def gather_plans(abstract, logical, specs):
    """Per-leaf ``((dim, mesh_axis), ...)`` all-gathers to run at the top of
    a shard_map body: every sharded dim EXCEPT the local-use output dims of
    the q/k/v/FFN-up projections (see module docstring)."""
    flat_a, tdef = jax.tree.flatten(abstract)
    paths = [jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_flatten_with_path(abstract)[0]]
    flat_l = tdef.flatten_up_to(logical)
    flat_s = tdef.flatten_up_to(specs)
    plans = []
    for a, l, s, ks in zip(flat_a, flat_l, flat_s, paths):
        if l is None:
            l = (None,) * a.ndim
        local = any(k in ks for k in _LOCAL_KEYS)
        plan = []
        for dim, axes in enumerate(tuple(s)):
            if axes is None:
                continue
            if local and l[dim] in _LOCAL_AXES:
                continue
            for ax in _axes_tuple(axes):
                plan.append((dim, ax))
        plans.append(tuple(plan))
    return jax.tree.unflatten(tdef, plans)


def gather_at_use(tree, plans):
    """Run each leaf's gather plan (inside a shard_map body). all_gather is
    pure data movement — bit-exact by construction."""
    def g(x, plan):
        for dim, ax in plan:
            x = jax.lax.all_gather(x, ax, axis=dim, tiled=True)
        return x
    return jax.tree.map(g, tree, plans)


def tp_dot(axis: str = MODEL_AXIS):
    """The ``dot`` hook for SPMD serving. Reproduces each site's default
    einsum exactly (including lm_head's f32 accumulation) so the sharded
    engine stays bit-comparable to the unsharded `dot=None` path; the two
    contraction-sharded sites gather their activations first — the shape
    test keeps replicated-fall-through weights (e.g. an odd d_ff) on the
    plain einsum."""
    def dot(a, w, name):
        if name in ("attn_q", "attn_k", "attn_v"):
            return jnp.einsum("bsd,dnh->bsnh", a, w)
        if name == "attn_o":
            if a.shape[2] != w.shape[0]:                  # local heads
                a = jax.lax.all_gather(a, axis, axis=2, tiled=True)
            return jnp.einsum("bsnh,nhd->bsd", a, w)
        if name in ("ffn_in", "ffn_gate"):
            return jnp.einsum("...d,df->...f", a, w)
        if name == "ffn_out":
            if a.shape[-1] != w.shape[0]:                 # local d_ff
                a = jax.lax.all_gather(a, axis, axis=a.ndim - 1,
                                       tiled=True)
            return jnp.einsum("...d,df->...f", a, w)
        if name == "lm_head":
            return jnp.einsum("bsd,dv->bsv", a, w,
                              preferred_element_type=F32)
        if name in ("moe_in", "moe_gate"):
            return jnp.einsum("ecd,edf->ecf", a, w)
        if name == "moe_out":
            return jnp.einsum("ecf,efd->ecd", a, w)
        raise ValueError(f"unknown dot site {name!r}")
    return dot


class SpmdEngine:
    """Sharding context the Engine holds when built with a mesh: param /
    pool placement plus the shard_map'd decode, chunk-prefill, whole-prompt
    prefill, pool-writer, and unembed closures.

    All jits share one contract: page table / tokens / positions replicated,
    params per ``specs_for`` (gathered at use where a contraction would
    split), pool sharded on ``kv_heads`` over ``model``. Every shard_map
    runs with ``check_vma=False``: the Pallas paged-attention call inside
    the bodies carries no varying-mesh-axes rule, and replicated outputs
    are replicated by construction (their inputs are all-gathered first).
    """

    def __init__(self, model, mesh: Mesh, *, kv_bits=None,
                 kernel: str = "auto", dot=None):
        if dot is not None:
            raise NotImplementedError(
                "sharded engine with a weight-quant dot hook: HAQ weight "
                "dicts have no logical specs yet (ROADMAP)")
        validate_mesh(model.cfg, mesh)
        self.model = model
        self.cfg = model.cfg
        self.mesh = mesh
        self.kernel = kernel
        self.kv_bits = kv_bits
        abstract = model.abstract_params()
        logical = model.logical_specs()
        self.param_pspecs = partition_specs(abstract, logical, mesh)
        self._plans = gather_plans(abstract, logical, self.param_pspecs)
        self.pool_pspecs = partition_specs(
            transformer.pool_specs(self.cfg, 2, 2, kv_bits=kv_bits),
            transformer.pool_axes(self.cfg, kv_bits), mesh)
        self.dot = tp_dot()

    # ----------------------------------------------------------- placement --
    def _named(self, pspecs):
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), pspecs)

    def shard_params(self, params):
        """Place params at rest: TP dims local, FSDP dims split over data."""
        return jax.device_put(params, self._named(self.param_pspecs))

    def pool_shardings(self):
        return self._named(self.pool_pspecs)

    def gathered(self, params):
        return gather_at_use(params, self._plans)

    def _cache_pspecs(self, cache):
        """Full-layout prefill caches: (G, B, Sp, K, hd) sharded on K."""
        return partition_specs(
            jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
                         if hasattr(a, "shape") else a, cache),
            jax.tree.map(lambda a: _CACHE_KV_AXES, cache), self.mesh)

    # ---------------------------------------------------------------- jits --
    def jit_decode(self):
        model, dot, kernel = self.model, self.dot, self.kernel

        def body(p, pool, pt, tok, pos):
            return model.decode_step_paged(self.gathered(p), pool, pt, tok,
                                           pos, dot=dot, kernel=kernel)

        return jax.jit(jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(self.param_pspecs, self.pool_pspecs, P(), P(), P()),
            out_specs=(P(), self.pool_pspecs), check_vma=False),
            donate_argnums=(1,))

    def jit_prefill_chunk(self):
        model, dot, kernel = self.model, self.dot, self.kernel

        def body(p, pool, pt, toks, pos):
            return model.prefill_chunk_paged(self.gathered(p), pool, pt,
                                             toks, pos, dot=dot,
                                             kernel=kernel)

        return jax.jit(jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(self.param_pspecs, self.pool_pspecs, P(), P(), P()),
            out_specs=(P(), self.pool_pspecs), check_vma=False),
            donate_argnums=(1,))

    def jit_unembed_row(self):
        model, dot = self.model, self.dot

        def body(p, h, idx):
            h = jnp.take_along_axis(h, idx.reshape(1, 1, 1), axis=1)
            return model.unembed(self.gathered(p), h, dot=dot)

        return jax.jit(jax.shard_map(
            body, mesh=self.mesh, in_specs=(self.param_pspecs, P(), P()),
            out_specs=P(), check_vma=False))

    def make_prefill(self, prefill_fn):
        """Whole-prompt (non-chunked) bucketed prefill: logits replicated,
        cache K-sharded so the pool writer scatters shard-locally. One jit
        per padding bucket, held in the engine's JitLRU like the unsharded
        path."""
        cache = self.model.cache_specs(1, 2)
        cspecs = self._cache_pspecs(
            {k: v for k, v in cache.items() if k.startswith("sub")})
        return jax.jit(jax.shard_map(
            prefill_fn, mesh=self.mesh,
            in_specs=(self.param_pspecs, P(), P()),
            out_specs=(P(), cspecs), check_vma=False))

    def jit_pool_writer(self, write_fn, cache):
        """shard_map'd span writer for one (n_pages, cache_len) shape:
        ``write_fn(pool, cache, idx) -> pool`` with the full-layout cache
        and the pool both sharded on kv_heads; the scatter at replicated
        page ids is purely local. Donation rides the engine's JitLRU entry
        exactly like the unsharded writer."""
        cspecs = self._cache_pspecs(cache)
        return jax.jit(jax.shard_map(
            write_fn, mesh=self.mesh,
            in_specs=(self.pool_pspecs, cspecs, P()),
            out_specs=self.pool_pspecs, check_vma=False),
            donate_argnums=(0,))

    # ------------------------------------------------------------ describe --
    def event_tags(self) -> dict:
        """Mesh tags stamped on every telemetry tick event
        (serving/telemetry): lets a Chrome trace / calibration report
        from a sharded run be told apart from — and grouped against —
        single-device runs. One host drives all shards (the page table
        and scheduler are replicated), so tags describe the mesh, not a
        shard index."""
        return {"mesh_model": self.mesh.shape.get(MODEL_AXIS, 1),
                "mesh_data": self.mesh.shape.get("data", 1),
                "mesh_devices": self.mesh.size}

    def describe(self) -> str:
        tp = self.mesh.shape.get(MODEL_AXIS, 1)
        dp = self.mesh.shape.get("data", 1)
        return (f"mesh(model={tp}, data={dp}): pool kv_heads/{tp}, "
                f"params at rest per specs_for (gather-at-use), "
                f"page table + scheduler replicated on host")
