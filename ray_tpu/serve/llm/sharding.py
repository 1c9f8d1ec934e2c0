"""Tensor-parallel sharding for the Serve-LLM engine.

Lowers an `EngineConfig` + a device mesh into the `NamedSharding`s the
engine's prefill/decode jits need, reusing the train-side rule table
(ray_tpu/parallel/sharding.py DEFAULT_RULES) so the serving path and the
training path place parameters identically — there is exactly one place
that knows "heads/qkv/mlp/vocab mean tp".

What gets sharded, and on which axis of the serve mesh:
- model params: by their logical axis names (qkv/heads/mlp/vocab -> tp;
  embed -> fsdp, size 1 on a serve mesh, i.e. replicated);
- the paged KV pool ``kv_pages`` [L, P, Hkv, page, 2*D]: the Hkv axis is
  split over tp — the page-major layout already keeps each kv head's
  pages contiguous, so a tp shard holds Hkv/tp heads of EVERY page and
  the block tables (page ids) stay global and replicated. Continuous
  batching, prefix caching and preemption therefore need no shard-local
  bookkeeping: one host-side allocator drives all shards;
- the decode carry ``slot_ids`` and every small host operand (block
  tables, lengths, sampling params, PRNG keys): replicated, so the fused
  decode scan stays device-resident with no host round-trips.

Per-shard page accounting: sharding the Hkv axis divides each page's
byte footprint by tp, so a fixed HBM budget affords tp× the pages — or
equivalently a model tp× bigger at the same page count. `page_accounting`
reports both views; `pages_for_budget` sizes `num_pages` from a per-chip
byte budget.

TPU caveat: the Pallas decode/flash kernels are single-device programs;
under GSPMD they would need a shard_map wrapper (future work). A sharded
engine therefore pins the jnp reference attention paths via the
PagedCache's static `ref_attention` field (models/llama.py), which XLA
partitions like any other einsum. Off-TPU backends already use those
paths. Likewise the engine is one process: tp is bounded by the chips
one host exposes (CHIPS_PER_HOST); multi-host tp needs a multi-process
engine (jax distributed init across the gang) — future work, rejected
loudly by `tp_bundles` rather than reserving chips a replica can't use.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

# Chips per TPU host (v5e/v6e hosts expose 4 chips); one SLICE_PACK
# bundle is one host's worth of a tensor-parallel gang.
CHIPS_PER_HOST = 4

# jax/flax imports stay inside functions (like engine.py): this module
# is imported by ray_tpu.serve.llm and must not drag jax into every
# worker spawn.


@dataclasses.dataclass
class ServeSharding:
    """Resolved sharding context for one engine: the mesh, the tp degree,
    and the rule table that maps logical param axes onto it (None = the
    train-side parallel.sharding.DEFAULT_RULES)."""

    mesh: Any                       # jax.sharding.Mesh
    tp: int
    rules: Optional[tuple] = None

    def _rules(self):
        if self.rules is not None:
            return self.rules
        from ...parallel.sharding import DEFAULT_RULES

        return DEFAULT_RULES

    # ------------------------------------------------------------ specs

    def kv_pages_sharding(self):
        """[L, P, Hkv, page, 2*D]: Hkv (axis 2) is the tp shard."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P(None, None, "tp", None, None))

    def replicated(self):
        """Small operands (carry, block tables, sampling arrays)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P())

    def param_shardings(self, model, example):
        """NamedShardings for the model's (unboxed) param tree, derived
        from the logical axis annotations via the shared rule table.
        `example` is an input to initialise shapes with: token ids, or
        hidden states for a slice of layers that does not start the
        model (its params keep the whole model's names and annotations,
        so one lowering serves every slice)."""
        import flax.linen as nn
        import jax

        abstract = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), example))
        logical = nn.get_partition_spec(abstract)
        return nn.logical_to_mesh_sharding(
            logical, self.mesh, self._rules())["params"]

    def shard_params(self, params, shardings):
        import jax

        return jax.tree.map(jax.device_put, params, shardings)

    # ------------------------------------------------------- validation

    def validate(self, model_cfg) -> None:
        """The Hkv axis of the page pool is the tp shard: it must divide
        evenly (a ragged head split would mis-tile every page), and so
        must the query heads feeding it."""
        if model_cfg.num_kv_heads % self.tp != 0:
            raise ValueError(
                f"num_kv_heads={model_cfg.num_kv_heads} is not divisible "
                f"by tp={self.tp}: the paged KV cache shards its Hkv axis "
                f"over tp, so tp must divide the kv head count (use tp in "
                f"{_divisors(model_cfg.num_kv_heads)})")
        if model_cfg.num_heads % self.tp != 0:
            raise ValueError(
                f"num_heads={model_cfg.num_heads} is not divisible by "
                f"tp={self.tp}: attention query heads shard over tp")

    # ------------------------------------------------------- accounting

    def page_accounting(self, config, model_cfg) -> Dict[str, Any]:
        """Per-shard view of the page pool (the number operators size
        HBM against): sharding Hkv divides each page's bytes by tp."""
        import jax.numpy as jnp

        itemsize = jnp.dtype(
            jnp.bfloat16 if config.dtype == "bfloat16" else jnp.float32
        ).itemsize
        page_bytes = (model_cfg.num_layers * model_cfg.num_kv_heads
                      * config.page_size * 2 * model_cfg.head_dim_
                      * itemsize)
        return {
            "tp": self.tp,
            "kv_heads_per_shard": model_cfg.num_kv_heads // self.tp,
            "page_bytes_global": page_bytes,
            "page_bytes_per_shard": page_bytes // self.tp,
            "pool_bytes_per_shard": (page_bytes // self.tp
                                     * config.num_pages),
        }


def _divisors(n: int) -> List[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def pages_for_budget(hbm_bytes_per_chip: int, page_size: int,
                     model_cfg, dtype_bytes: int = 2,
                     tp: int = 1) -> int:
    """num_pages affordable from a per-chip KV byte budget: each chip
    holds Hkv/tp heads of every page, so the budget buys tp× the pages a
    single chip could hold."""
    page_bytes = (model_cfg.num_layers * model_cfg.num_kv_heads
                  * page_size * 2 * model_cfg.head_dim_ * dtype_bytes)
    return max(1, hbm_bytes_per_chip * tp // page_bytes)


def tp_bundles(tp: int,
               chips_per_host: int = CHIPS_PER_HOST) -> List[Dict[str, float]]:
    """Placement-group bundle reserving a tp-chip gang on ONE TPU host
    (SLICE_PACK places it on a host of an ICI slice). The engine is a
    single process, so tp beyond one host's chips cannot run yet —
    reject it here instead of reserving chips the replica can never
    reach (multi-host tp = multi-process engine, future work)."""
    if tp > chips_per_host:
        raise ValueError(
            f"tp={tp} exceeds the {chips_per_host} chips one host "
            f"exposes; the single-process engine cannot span hosts "
            f"(multi-host tensor parallelism is not supported yet)")
    return [{"TPU": float(tp)}]


def pp_bundles(pp: int, tp: int = 1,
               chips_per_host: int = CHIPS_PER_HOST) -> List[Dict[str, float]]:
    """Placement-group bundles for a pipeline-parallel stage gang: one
    tp-chip bundle PER STAGE. Each stage engine is its own worker
    process with a single-host tp mesh, so per-stage tp keeps the
    one-host bound tp_bundles enforces — but stages themselves may (and
    at pp*tp > chips_per_host must) land on different hosts. SLICE_PACK
    walks the gang along the ICI snake path (runtime/topology.py
    ici_path via scheduling), so bundle order == stage order ==
    neighbouring hosts: the rank k -> k+1 activation channel crosses
    one ICI hop, not the slice diameter."""
    if pp < 1:
        raise ValueError(f"pp must be >= 1, got pp={pp}")
    if tp > chips_per_host:
        raise ValueError(
            f"tp={tp} exceeds the {chips_per_host} chips one host "
            f"exposes; a pipeline stage is a single-process tp engine, "
            f"so scale further with pp (stages multiply chips, tp "
            f"cannot widen past one host)")
    return [{"TPU": float(tp)} for _ in range(pp)]


def resolve_serve_mesh(mesh=None, tp: int = 1,
                       devices=None) -> Optional[ServeSharding]:
    """Normalize the engine's mesh input into a ServeSharding (or None
    for the single-device fast path).

    Accepts:
    - None with tp<=1: single-device engine (no sharding machinery);
    - an int tp (or tp= kwarg): builds a [1,1,1,1,1,tp] mesh over the
      first tp local devices;
    - a jax.sharding.Mesh: must carry a "tp" axis (the standard AXES
      layout from parallel/mesh.py); its tp extent is the shard degree.
      A 1-device mesh degrades to the single-device path.
    """
    if mesh is None and isinstance(tp, int) and tp <= 1:
        return None

    import jax
    from jax.sharding import Mesh

    from ...parallel.mesh import AXES, MeshConfig, create_mesh

    if isinstance(mesh, int):  # LLMEngine(mesh=4) shorthand
        tp, mesh = mesh, None
    if mesh is None:
        devices = list(devices if devices is not None else jax.devices())
        if len(devices) < tp:
            raise ValueError(
                f"tp={tp} needs {tp} devices, found {len(devices)}")
        mesh = create_mesh(
            MeshConfig(pp=1, dp=1, fsdp=1, sp=1, ep=1, tp=tp),
            devices=devices[:tp])
    if not isinstance(mesh, Mesh):
        raise TypeError(f"mesh must be a jax.sharding.Mesh or int tp "
                        f"degree, got {type(mesh).__name__}")
    if "tp" not in mesh.axis_names:
        raise ValueError(
            f"serve mesh must carry a 'tp' axis (got {mesh.axis_names}); "
            f"build it with parallel.mesh.create_mesh(MeshConfig(tp=...)) "
            f"— standard axes are {AXES}")
    tp_degree = dict(zip(mesh.axis_names, mesh.devices.shape))["tp"]
    if mesh.size == 1:
        return None  # degenerate mesh: keep the unsharded fast path
    return ServeSharding(mesh=mesh, tp=tp_degree)
