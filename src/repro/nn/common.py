"""Model configuration and sharding context shared by the whole nn stack."""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.quant import QuantConfig  # noqa: F401 — re-exported config knob


# ---------------------------------------------------------------------------
# Sharding context: model code calls shard(x, ...) with *logical* axes; the
# trainer / dry-run installs a mesh so the constraints become real. With no
# mesh installed (unit tests, CPU smokes) shard() is the identity.
# ---------------------------------------------------------------------------

_MESH: contextvars.ContextVar[Optional[jax.sharding.Mesh]] = \
    contextvars.ContextVar("repro_mesh", default=None)

# logical name -> mesh axis name (or tuple of axes), installed with the mesh
_AXIS_RULES: contextvars.ContextVar[dict] = \
    contextvars.ContextVar("repro_axis_rules", default={})


@contextlib.contextmanager
def mesh_context(mesh: jax.sharding.Mesh, axis_rules: dict):
    t1 = _MESH.set(mesh)
    t2 = _AXIS_RULES.set(dict(axis_rules))
    try:
        yield
    finally:
        _MESH.reset(t1)
        _AXIS_RULES.reset(t2)


def current_mesh() -> Optional[jax.sharding.Mesh]:
    return _MESH.get()


def logical_to_spec(*logical: Optional[str]) -> P:
    rules = _AXIS_RULES.get()
    axes = []
    for name in logical:
        ax = rules.get(name) if name is not None else None
        axes.append(ax)
    return P(*axes)


def shard(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """Constrain ``x``'s sharding by logical axis names (no-op without mesh)."""
    mesh = _MESH.get()
    if mesh is None:
        return x
    spec = logical_to_spec(*logical)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


def junction_shard_ctx(pattern):
    """(mesh, axis) when the sharded block-sparse junction path applies
    under the installed mesh/rules, else ``None``.

    The decision is the runtime side of the policy's ``"slab"`` rule: the
    rule must resolve to a single mesh axis of size > 1 and the pattern's
    block-rows must split evenly over it (``can_partition`` — the same
    divisibility ``sanitize`` applies to the slab's storage sharding, so
    compute partition and weight chunks always agree)."""
    mesh = _MESH.get()
    if mesh is None or pattern is None:
        return None
    ax = _AXIS_RULES.get().get("slab")
    if not isinstance(ax, str) or ax not in mesh.axis_names:
        return None
    from ..core.block_pattern import can_partition
    if not can_partition(pattern, int(mesh.shape[ax])):
        return None
    return mesh, ax


def junction_shard_kwargs(pattern) -> dict:
    """``csd_matmul`` kwargs selecting the sharded junction path — the ONE
    place the gating decision plus kwarg spelling lives, shared by every
    junction call site (``nn.layers``, ``nn.ffn``, ``core.sparse_linear``).
    With a mesh installed but no shard for this junction, only the mesh
    is passed (the kernel then runs whole on every device); with no mesh,
    ``{}``."""
    ctx = junction_shard_ctx(pattern)
    if ctx is None:
        mesh = _MESH.get()
        return {} if mesh is None or pattern is None else {"mesh": mesh}
    return {"mesh": ctx[0], "axis": ctx[1]}


# ---------------------------------------------------------------------------
# Configuration
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SparsityConfig:
    """Where and how pre-defined sparsity is applied inside a model.

    ``rho_ffn`` follows the paper's per-junction density guideline: the
    FFN up/gate junction gets ``rho_ffn[0]`` and the down junction
    ``rho_ffn[1]`` (trend 3: later junctions denser).
    """

    enabled: bool = False
    rho_ffn: Tuple[float, float] = (0.5, 0.75)
    rho_attn: Optional[float] = None  # None = attention projections dense
    # MoE expert junctions (up/gate/down of every routed expert) become
    # pre-defined block-sparse too, executed through the batched
    # (expert-major) csd_matmul path with one pattern shared across
    # experts. Densities follow rho_ffn. Off by default: expert matmuls
    # keep the dense stacked-einsum form unless opted in.
    moe_sparsity: bool = False
    method: str = "clashfree"
    cf_type: int = 1
    dither: bool = False
    # Block aspect adopted after the §Perf hillclimb: slot-gather traffic
    # scales 1/block_out and accumulator traffic 1/block_in, so tall-wide
    # (256 x 1024) tiles cut the sparse-FFN HBM bytes 2.2x vs the square
    # 128x128 MXU-tile baseline (EXPERIMENTS.md §Perf, iterations 2-3).
    block_in: int = 256
    block_out: int = 1024
    seed: int = 0
    # auto = pallas on TPU, xla elsewhere; all junctions route through the
    # one csd_matmul primitive either way
    backend: str = "auto"  # auto | xla | pallas
    # inference-path int8 weight/KV quantization (core.quant.QuantConfig);
    # None = full width. Training always runs full width — the engine (or
    # an explicit quantize_tree call) applies this once at load.
    quant: Optional["QuantConfig"] = None


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_routed: int
    top_k: int
    n_shared: int = 0
    d_expert: int = 0           # per-expert hidden size
    capacity_factor: float = 1.25
    router_zloss: float = 1e-3
    first_layer_dense: bool = False   # deepseek-moe: layer 0 is dense FFN
    dense_d_ff: int = 0               # hidden size of that dense layer


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    n_groups: int = 1
    chunk: int = 256
    a_init_range: Tuple[float, float] = (1.0, 16.0)
    dt_limit: Tuple[float, float] = (1e-3, 1e2)


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """zamba2-style: mamba backbone + a single shared attention block applied
    every ``period`` layers (parameter sharing across applications)."""
    period: int = 6
    shared_d_ff: int = 8192
    concat_embedding: bool = True  # shared block sees [h, embedding] (2*d)


@dataclasses.dataclass(frozen=True)
class EncDecConfig:
    n_encoder_layers: int = 12
    n_decoder_layers: int = 12


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    n_layers: int = 4
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    head_dim: int = 0            # 0 -> d_model // n_heads
    d_ff: int = 1024
    vocab_size: int = 32000
    max_seq_len: int = 8192

    # block structure
    block_kind: str = "attn"     # attn | mamba | hybrid (see layer_pattern)
    layer_pattern: Tuple[str, ...] = ()  # per-layer kinds, cycled; () = all attn
    attn_window: Optional[int] = None    # sliding window for 'local' layers
    local_global_ratio: int = 0          # k local : 1 global (0 = all global)
    logit_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    post_norms: bool = False     # gemma2/3 sandwich norms
    act: str = "silu"            # silu | gelu | relu
    ffn_gated: bool = True       # SwiGLU/GeGLU vs plain MLP
    tie_embeddings: bool = True
    scale_embed: bool = False    # gemma multiplies embeddings by sqrt(d)

    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    hybrid: Optional[HybridConfig] = None
    enc_dec: Optional[EncDecConfig] = None
    input_mode: str = "tokens"   # tokens | embeddings (audio/vlm frontends)
    frontend_dim: int = 0        # embedding dim delivered by the stub frontend

    sparsity: SparsityConfig = dataclasses.field(default_factory=SparsityConfig)

    dtype: str = "bfloat16"      # activation/compute dtype
    param_dtype: str = "float32"
    remat: bool = True
    attn_chunk: int = 512        # q-chunk for the XLA flash scan
    attn_kv_chunk: int = 1024    # inner flash KV chunk for long sequences
    loss_chunk: int = 512        # seq chunk for cross-entropy

    def __post_init__(self):
        if self.head_dim == 0 and self.n_heads:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)

    # -- derived -----------------------------------------------------------

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """Resolved per-layer kind: 'global', 'local', 'mamba'."""
        if self.layer_pattern:
            pat = self.layer_pattern
            return tuple(pat[i % len(pat)] for i in range(self.n_layers))
        if self.block_kind == "mamba":
            return ("mamba",) * self.n_layers
        if self.local_global_ratio > 0:
            k = self.local_global_ratio
            out = []
            for i in range(self.n_layers):
                out.append("local" if (i % (k + 1)) != k else "global")
            return tuple(out)
        return ("global",) * self.n_layers

    def with_(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One assigned input-shape cell."""
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


def dtype_of(cfg: ModelConfig) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


def param_dtype_of(cfg: ModelConfig) -> jnp.dtype:
    return jnp.dtype(cfg.param_dtype)
