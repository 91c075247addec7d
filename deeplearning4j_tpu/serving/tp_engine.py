"""Tensor-parallel serving plan: shard ONE decode engine over a `tp` mesh.

ROADMAP item 3(a): training composes dp×tp×pp in one mesh, but the
serving tier was single-device — a model whose weights or KV pool
exceed one chip's HBM simply could not serve. This module is the
serving-side tensor parallelism: a `TPPlan` shards a `GPTPlan` net
Megatron-style (Shoeybi et al., 2019) over a named `tp` mesh axis and
wraps the engine's jitted step closures in `shard_map`, so the whole
existing serving stack — chunked prefill, prefix-cache sharing,
speculative verify, int8 KV pools, the Pallas paged-attention kernel —
rides the sharded engine untouched.

**Sharding layout** (per transformer block, degree N):

| tensor | layout | shard |
|---|---|---|
| `Wqkv` | (d, d + 2·Hkv·hd), columns permuted to [Q_t ‖ K_t ‖ V_t] | columns over `tp` |
| `bqkv` | same permutation | over `tp` |
| `Wo`   | (d, d), rows ordered by query head | rows over `tp` |
| `bo`   | replicated, added AFTER the all-reduce | — |
| `W1`/`W3`/`b1` | column-parallel FFN in | columns over `tp` |
| `W2`   | row-parallel FFN out | rows over `tp` |
| `b2`   | replicated, added AFTER the all-reduce | — |
| embeddings / LNs / logits head | replicated | — |
| K/V page pools (+ int8 scale sidecars) | `(P+1, Hkv, …)` | head axis over `tp` |

Exactly TWO all-reduces per block per token (after out-proj, after
FFN-out — `models.transformer._psum_partial`), the Megatron minimum.
Each device owns `Hkv/N` heads of EVERY page, so the page table,
free list, refcounts, prefix-cache promotions and trash-page masking
stay host-global and byte-identical to the single-device engine: page
management is head-agnostic. Attention itself is embarrassingly
parallel over heads — the per-device body is the EXISTING kernel (or
gather fallback) at `Hkv/N`, and GQA grouping is preserved because
`(H/N)/(Hkv/N) == H/Hkv`.

**Why column permutation.** `Wqkv` packs [Q | K | V] along its output
axis; a plain column split would hand device t an arbitrary mix of Q
and K columns. Permuting columns so device t's contiguous block is
[Q_t | K_t | V_t] keeps the per-device projection a single matmul whose
output slices exactly like the global one (`_block_heads(shard=N)`),
at zero runtime cost — the permutation happens once at `shard_params`
time on host.

**Parity.** The sharded computation is the same math with one changed
reduction: row-parallel contractions accumulate d/N-length partials
then sum across devices. f32 argmax-exact parity with the single-device
engine is pinned in `tests/test_tp_engine.py` across chunked prefill ×
prefix hits × speculative × GQA × int8 KV on a forced-host-device mesh
(`XLA_FLAGS=--xla_force_host_platform_device_count=8`).
"""
from __future__ import annotations

import numpy as np

TP_AXIS = "tp"

# keys sharded along their OUTPUT axis (column-parallel)
_COL_KEYS = ("Wqkv", "W1", "W3")
_COL_BIAS_KEYS = ("bqkv", "b1", "b3")
# keys sharded along their INPUT axis (row-parallel; bias replicated
# and added after the psum — see models.transformer)
_ROW_KEYS = ("Wo", "W2")

# one Mesh per degree per process: the conftest session fixture warms
# this once so every tier-1 TP test shares a mesh instead of re-paying
# mesh construction (and XLA device queries) per engine build
_MESH_CACHE: dict = {}


def tp_mesh(degree: int):
    """The serving `tp` mesh over the first `degree` local devices,
    cached per process. Raises ValueError (typed, at construction —
    never a trace error) when the platform doesn't expose enough
    devices; on CPU hosts the fix is
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
    mesh = _MESH_CACHE.get(degree)
    if mesh is not None:
        return mesh
    import jax

    from deeplearning4j_tpu.parallel.mesh import make_mesh

    devs = jax.devices()
    if len(devs) < degree:
        raise ValueError(
            f"parallel={{'tp': {degree}}} needs {degree} devices but the "
            f"platform exposes {len(devs)} — on a CPU host set "
            "XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{degree} (the tier-1 conftest does)")
    mesh = make_mesh({TP_AXIS: degree}, devices=devs[:degree])
    _MESH_CACHE[degree] = mesh
    return mesh


class TPPlan:
    """Sharding plan for one `GPTPlan` net at tensor-parallel degree N:
    validates the geometry at CONSTRUCTION (typed ValueErrors, never
    trace errors), owns the mesh and per-argument PartitionSpec trees,
    permutes+places params, and wraps step closures in
    `jit(shard_map(...))` with the engine's donation discipline."""

    def __init__(self, net, plan, degree: int):
        from jax.sharding import PartitionSpec as P

        if not isinstance(degree, int) or degree < 2:
            raise ValueError(
                f"tensor-parallel degree must be an int >= 2, got "
                f"{degree!r} (tp=1 is the single-device engine — omit "
                "parallel= instead)")
        self.degree = degree
        self.axis = TP_AXIS
        self.mesh = tp_mesh(degree)
        self.plan = plan
        params = net._params
        # per-layer-index spec: dict-of-specs for transformer blocks,
        # replicated prefix for everything else (embedding, LNs, head)
        specs: list = [P()] * len(params)
        self._perms: dict = {}
        for i in plan.block_is:
            layer = plan.layers[i]
            if getattr(layer, "moe_experts", 0) > 0:
                raise ValueError(
                    "parallel={'tp': N} does not compose with MoE blocks "
                    "(expert parallelism is its own axis) — serve the "
                    "dense net or drop parallel=")
            H, Hkv = layer.n_heads, layer._kv_heads
            if H % degree or Hkv % degree:
                raise ValueError(
                    f"tp={degree} must divide the head counts of every "
                    f"block: block {i} has n_heads={H}, kv_heads={Hkv}")
            p = params[i]
            f = int(p["W1"].shape[1]) if "W1" in p else 0
            if f % degree:
                raise ValueError(
                    f"tp={degree} must divide the FFN width of every "
                    f"block: block {i} has ffn={f}")
            d = int(layer.n_out)
            hd = d // H
            self._perms[i] = self._qkv_perm(d, H, Hkv, hd, degree)
            specs[i] = {
                k: (P(None, TP_AXIS) if k in _COL_KEYS
                    else P(TP_AXIS) if k in _COL_BIAS_KEYS
                    else P(TP_AXIS, None) if k in _ROW_KEYS
                    else P())
                for k in p}
        self.param_specs = specs

    @staticmethod
    def _qkv_perm(d, H, Hkv, hd, n):
        """Column permutation of the packed [Q | K | V] output axis so
        device t's contiguous axis-1 block is [Q_t | K_t | V_t]."""
        Hl, Hkvl = H // n, Hkv // n
        k0, v0 = d, d + Hkv * hd
        idx = []
        for t in range(n):
            idx.extend(range(t * Hl * hd, (t + 1) * Hl * hd))
            idx.extend(range(k0 + t * Hkvl * hd, k0 + (t + 1) * Hkvl * hd))
            idx.extend(range(v0 + t * Hkvl * hd, v0 + (t + 1) * Hkvl * hd))
        return np.asarray(idx, np.int64)

    # -- placement ---------------------------------------------------------
    def shard_params(self, params):
        """Permute + place the net's params once per (re)build. Returns
        a NEW list — `net._params` stays the untouched host-layout copy
        (weight swaps, checkpoints, and the parity oracle all read it),
        so a reload reshards from clean state."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        repl = NamedSharding(self.mesh, P())
        out = []
        for i, p in enumerate(params):
            spec = self.param_specs[i]
            if isinstance(spec, dict):
                perm = self._perms[i]
                q = {}
                for k, v in p.items():
                    if k == "Wqkv":
                        v = v[:, perm]
                    elif k == "bqkv":
                        v = v[perm]
                    q[k] = jax.device_put(
                        v, NamedSharding(self.mesh, spec[k]))
                out.append(q)
            else:
                out.append(jax.tree_util.tree_map(
                    lambda v: jax.device_put(v, repl), p))
        return out

    def shard_pool(self, x):
        """Place one page-pool (or scale-sidecar) array with its head
        axis (axis 1 in every pool layout) split over `tp`."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = P(*([None, TP_AXIS] + [None] * (x.ndim - 2)))
        return jax.device_put(x, NamedSharding(self.mesh, spec))

    # -- shard_map wrapping ------------------------------------------------
    def in_specs(self, n: int, params_at: int = 0, caches_at: int = 1):
        """Per-argument spec tuple: the params tree-of-specs, the pools
        as a `P(None, 'tp')` pytree prefix (head axis is axis 1 of every
        pool leaf, trailing dims unsharded), everything else — page
        table, slot state, scalars — replicated."""
        from jax.sharding import PartitionSpec as P

        specs = [P()] * n
        specs[params_at] = self.param_specs
        specs[caches_at] = P(None, TP_AXIS)
        return tuple(specs)

    def out_specs(self, n: int, caches_at: int = 0):
        from jax.sharding import PartitionSpec as P

        specs = [P(None, TP_AXIS) if j == caches_at else P()
                 for j in range(n)]
        return specs[0] if n == 1 else tuple(specs)

    def shard(self, fn, *, n_in: int, n_out: int,
              params_at: int = 0, caches_at: int = 1,
              caches_out_at: int = 0):
        """`shard_map` a step closure over the tp mesh. Callers jit the
        result with their own donation discipline — the literal
        ``x = jax.jit(tp.shard(f, ...), donate_argnums=...)`` assign is
        exactly the form graftlint's donation rule tracks, so the
        donated-sharded-pool hazard stays linted. Non-pool outputs are
        declared replicated: every device runs the identical
        deterministic math on replicated inputs after each psum, so
        replication checking off (the repo's established shard_map
        idiom — parallel/sequence.py) is sound here."""
        import jax

        return jax.shard_map(
            fn, mesh=self.mesh,
            in_specs=self.in_specs(n_in, params_at, caches_at),
            out_specs=self.out_specs(n_out, caches_out_at),
            check_vma=False)

    # -- byte accounting ---------------------------------------------------
    def weight_bytes_per_chip(self, params) -> int:
        """Per-chip weight residency: sharded matmul slices divide by
        the degree, replicated tensors don't — the bench's
        `tp_max_model_bytes_per_chip` numerator."""
        import jax
        from jax.sharding import PartitionSpec as P

        total = 0
        for i, p in enumerate(params):
            spec = self.param_specs[i]
            if isinstance(spec, dict):
                for k, v in p.items():
                    total += v.nbytes // (self.degree
                                          if spec[k] != P() else 1)
            else:
                total += sum(x.nbytes
                             for x in jax.tree_util.tree_leaves(p))
        return total
