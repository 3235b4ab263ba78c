"""Configuration dataclasses for the FlexJAX framework.

Everything in the framework is driven by three frozen dataclasses:

* :class:`ModelConfig`   — architecture hyperparameters (one per ``--arch`` id).
* :class:`ShapeConfig`   — an (input-shape × step-kind) workload cell.
* :class:`TrainConfig`   — optimizer / loop / fault-tolerance settings.

Configs are plain data: no jax imports happen here, so importing a config never
touches device state (required for the 512-device dry-run bootstrap order).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any


@dataclass(frozen=True)
class ModelConfig:
    """Architecture hyperparameters.

    ``family`` selects the block stack:
      - ``dense``   : pre-norm GQA decoder (llama-style).
      - ``moe``     : dense attention + token-choice top-k MoE FFN.
      - ``ssm``     : attention-free Mamba2 (SSD) stack.
      - ``hybrid``  : Mamba2 backbone + shared attention block every
                      ``attn_every`` layers (Zamba2-style).
      - ``encdec``  : encoder-decoder with cross-attention (Seamless backbone;
                      modality frontend is a stub that supplies precomputed
                      frame embeddings).
      - ``vlm``     : decoder with M-RoPE (Qwen2-VL backbone; vision frontend
                      stubbed as precomputed patch embeddings).
    """

    name: str
    family: str
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int

    # --- SSM (mamba2 / zamba2) ---
    ssm_state: int = 0
    ssm_headdim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 256
    ssm_conv_width: int = 4

    # --- MoE ---
    num_experts: int = 0
    moe_top_k: int = 0
    capacity_factor: float = 1.25
    # d_ff above is the *per-expert* hidden width for MoE families.

    # --- hybrid (zamba2) ---
    attn_every: int = 0  # insert the shared attention block every k layers

    # --- encoder-decoder (seamless) ---
    enc_layers: int = 0

    # --- positional encoding ---
    rope_theta: float = 10_000.0
    use_mrope: bool = False  # Qwen2-VL M-RoPE (3 position streams)

    # --- misc ---
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    # Sliding-window / local attention width (0 = full causal). Used by the
    # beyond-paper perf work; full configs default to the published attention.
    attn_window: int = 0
    source: str = ""  # provenance string "[arXiv:... ; tier]"

    # ------------------------------------------------------------------ #
    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def sub_quadratic(self) -> bool:
        """May run the 512k-context decode cell (SSM / hybrid only)."""
        return self.family in ("ssm", "hybrid")

    @property
    def is_encoder_decoder(self) -> bool:
        return self.family == "encdec"

    @property
    def d_inner(self) -> int:
        """Mamba2 inner width."""
        return self.ssm_expand * self.d_model

    @property
    def ssm_nheads(self) -> int:
        return self.d_inner // self.ssm_headdim if self.ssm_headdim else 0

    def replace(self, **kw: Any) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # Parameter count estimate (used by roofline MODEL_FLOPS = 6·N·D).
    def param_count(self, active_only: bool = False) -> int:
        d, v = self.d_model, self.vocab_size
        emb = v * d * (1 if self.tie_embeddings else 2)
        per_attn = d * (self.num_heads * self.head_dim) \
            + 2 * d * (self.num_kv_heads * self.head_dim) \
            + (self.num_heads * self.head_dim) * d
        per_dense_mlp = 3 * d * self.d_ff
        n = emb
        if self.family in ("dense", "vlm"):
            n += self.num_layers * (per_attn + per_dense_mlp)
        elif self.family == "moe":
            e = self.moe_top_k if active_only else self.num_experts
            n += self.num_layers * (per_attn + e * 3 * d * self.d_ff)
        elif self.family == "ssm":
            din = self.d_inner
            per = d * (2 * din + 2 * self.ssm_state * 0)  # in_proj (z,x)
            per += d * din  # out_proj
            per += din * 2 * self.ssm_state  # B,C projections (per head group)
            per += din * 1  # dt proj
            n += self.num_layers * per
        elif self.family == "hybrid":
            din = self.d_inner
            per = d * 2 * din + d * din + din * 2 * self.ssm_state + din
            n += self.num_layers * per
            n_attn_blocks = 1  # shared weights
            n += n_attn_blocks * (per_attn + per_dense_mlp)
        elif self.family == "encdec":
            n += self.enc_layers * (per_attn + per_dense_mlp)
            n += self.num_layers * (2 * per_attn + per_dense_mlp)  # self+cross
        return n


@dataclass(frozen=True)
class ShapeConfig:
    """One workload cell: which step function is lowered and its shapes.

    ``kind``:
      - ``train``   : ``train_step`` over (global_batch, seq_len) tokens.
      - ``prefill`` : ``prefill_step`` — forward pass building a KV cache.
      - ``decode``  : ``serve_step`` — ONE new token against a KV cache of
                      ``seq_len`` (the assignment's decode_*/long_* semantics).
    """

    name: str
    kind: str
    seq_len: int
    global_batch: int

    @property
    def tokens(self) -> int:
        """Tokens *processed* per step (decode processes batch×1)."""
        if self.kind == "decode":
            return self.global_batch
        return self.global_batch * self.seq_len


# The four assigned shapes (identical across the LM pool).
SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32_768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524_288, 1),
}


@dataclass(frozen=True)
class TrainConfig:
    """Optimizer + training-loop settings."""

    optimizer: str = "flexa"  # "flexa" | "adamw"
    # --- FLEXA (Algorithm 1) ---
    flexa_rho: float = 0.5          # greedy selection factor ρ ∈ (0, 1]
    flexa_gamma0: float = 0.9       # γ⁰ for Eq. (4)
    flexa_theta: float = 1e-5       # θ  for Eq. (4)
    flexa_tau0: float = 1.0         # initial proximal weight τᵢ
    flexa_l1: float = 0.0           # c in G(x)=c‖x‖₁ (0 ⇒ G≡0)
    flexa_diag_q: bool = False      # diagonal Qᵢ curvature (beyond-paper)
    flexa_tau_adapt: bool = True    # double/halve rule from §4
    flexa_select: str = "greedy"    # "greedy" | "all" (full Jacobi)
    # --- AdamW baseline ---
    lr: float = 3e-4
    betas: tuple = (0.9, 0.95)
    weight_decay: float = 0.1
    # --- loop ---
    steps: int = 100
    log_every: int = 10
    seed: int = 0
    microbatch: int = 0             # 0 ⇒ no gradient accumulation
    remat: bool = True
    # --- fault tolerance ---
    ckpt_dir: str = ""
    ckpt_every: int = 50
    ckpt_keep: int = 3
    ckpt_async: bool = True
    resume: bool = True
    # --- distributed optimization tricks ---
    grad_compression: str = "none"  # "none" | "topk" | "int8"
    grad_topk_frac: float = 0.1
    pipeline: bool = False          # GPipe over the data axis (dense/vlm)
    pp_microbatches: int = 16
    # Activation-sharding strategy for train steps:
    #   "tp"    — TP+SP over `model` (default; best for small per-device
    #             batch quotas and inference);
    #   "zero3" — batch over BOTH axes, weights gathered per layer
    #             (ZeRO-3); wins when per-device activations ≪ weights,
    #             i.e. large global batch + deep dense models.
    strategy: str = "tp"


@dataclass(frozen=True)
class SolverConfig:
    """Settings for the paper-faithful convex solver (Algorithm 1)."""

    rho: float = 0.5
    gamma0: float = 0.9
    theta: float = 1e-5
    tau0: float = 0.0               # 0 ⇒ paper default tr(AᵀA)/2n
    tau_adapt: bool = True
    tau_grow: float = 2.0
    tau_shrink: float = 0.5
    tau_patience: int = 10
    surrogate: str = "exact_block"  # "linear" | "exact_block" | "newton_cg"
    inexact_alpha1: float = 0.0     # εᵏ schedule (0 ⇒ exact subproblems)
    inexact_alpha2: float = 1.0
    max_iters: int = 2_000
    tol: float = 1e-6               # stop when ‖x̂(x)−x‖∞ ≤ tol
    jacobi: bool = False            # True ⇒ Sᵏ = 𝒩 (full parallel Jacobi)
    # --- Step S.3 selection rule (repro.core.selection.make_mask) ---
    # "greedy" (paper FPA) | "full" | "southwell" | "topk" | "random" |
    # "hybrid" | "cyclic".  random/hybrid are the arXiv:1407.4504 sketch
    # rules; cyclic is the essentially-cyclic shuffled round-robin.
    selection: str = "greedy"
    sel_p: float = 0.25             # Bernoulli sketch probability
    sel_k: int = 8                  # k for the topk rule
    sel_chunks: int = 4             # cycle length for the cyclic rule
    seed: int = 0                   # PRNG seed for randomized selection


@dataclass(frozen=True)
class ServeConfig:
    """Settings for the solver serving engines (``repro.serve``): the
    continuous-batching engine (``ContinuousSolverEngine``) and its
    mesh-sharded form (``MeshServeEngine``) read the slab/scheduler
    knobs.  Frozen + hashable so a config can ride inside compile-cache
    keys if a runtime ever specializes on it.
    """

    # --- continuous engine ---
    slab_capacity: int = 8      # live slots per (family × shape) slab
    chunk_iters: int = 16       # FLEXA iterations per compiled chunk step
    # Admission-queue ordering: "fifo" (arrival order) | "priority"
    # (higher SolveRequest.priority first) | "deadline" (earliest
    # SolveRequest.deadline first; deadline-less requests last).
    policy: str = "fifo"
    # How many (family × shape) slabs one scheduler tick services, in
    # round-robin rotation across ticks (0 = all of them).  With > 1
    # distinct signatures live, the rotation guarantees every slab is
    # serviced at least once every ceil(n_slabs / slabs_per_tick) ticks
    # — no signature can starve behind a chatty one, whatever order the
    # slabs were created in.
    slabs_per_tick: int = 0
    # --- mesh engine (repro.serve.mesh.MeshServeEngine) ---
    # Devices the slab shards over (0 = every visible jax device).  On
    # CPU, multiple host devices come from
    # XLA_FLAGS=--xla_force_host_platform_device_count=N set before jax
    # initializes.  ``slab_capacity`` is PER DEVICE in the mesh engine:
    # the sharded slab holds mesh_devices * slab_capacity slots.
    mesh_devices: int = 0
    # Shared-queue → per-device-queue routing: "least_loaded" (fewest
    # live slots + queued requests, lowest device index tie-break) |
    # "round_robin" (cyclic cursor).
    mesh_routing: str = "least_loaded"
    # A device with a free slot and an EMPTY local queue steals from the
    # longest other queue holding >= steal_threshold requests (it never
    # steals while it has local work — the steal-only-when-idle
    # invariant the property tests pin).
    steal_threshold: int = 1
    # Drain-tail slab compaction: when the admission queue is empty and
    # the live-slot count drops a power-of-two capacity bucket, migrate
    # the stragglers into a narrower slab (and grow back on new
    # arrivals).  Off by default: migration retraces the chunk program
    # at each capacity, so trajectories agree with the fixed-capacity
    # run to solver tolerance (≤1e-5), not bitwise.  Continuous engine
    # only (mesh slabs keep their per-device geometry).
    compact_drain: bool = False
    # Numerical-health watchdog (repro.obs.health): the chunk stepper
    # additionally computes per-slot health verdicts (non-finite x/stat,
    # stationarity stall) on device and the engines quarantine unhealthy
    # slots — evicted with status="diverged"/"stalled" instead of
    # spinning to max_iters.  Off by default: the stepper then builds
    # the exact pre-watchdog program (bitwise-identical by
    # construction).  With the watchdog on, healthy workloads still
    # replay bitwise-identically — health flags read the iteration
    # outputs but never feed back into the iteration math.
    watchdog: bool = False
    # Stall patience H: quarantine a slot once its termination stat
    # ‖x̂(x)−x‖∞ has failed to decrease for H consecutive chunks.
    # Quarantine lands within H+1 chunks of admission (the first chunk
    # after admission always counts as a decrease from +inf).
    stall_patience: int = 10


@dataclass(frozen=True)
class ClientConfig:
    """One config for the one front door (``repro.client.FlexaClient``).

    Composes the two concerns every execution backend shares — the
    solver hyperparameters/budget (:class:`SolverConfig`) and the
    serving-runtime knobs (:class:`ServeConfig`) — plus the backend
    choice itself, so a workload is fully described by (spec, config)
    and switching ``backend`` can never change anything else.
    """

    solver: SolverConfig = field(default_factory=SolverConfig)
    serve: ServeConfig = field(default_factory=ServeConfig)
    # Execution backend: "inline" (facade / solve_batched in-process) |
    # "continuous" (ContinuousSolverEngine slot slabs) | "mesh"
    # (device-mesh slabs) |
    # "remote" (a repro.remote solver-service process over HTTP).
    # repro.client.available_backends() lists the registry.
    backend: str = "inline"
    # Base URL of the solver service the "remote" backend talks to,
    # e.g. "http://127.0.0.1:8781" — required when backend="remote",
    # ignored otherwise.
    remote_url: str = ""
    # Tenant identity the remote server applies quotas/SLO policy to
    # ("" = the server's default tenant).
    remote_tenant: str = ""
    # SLO class requested from the remote server ("" = the server's
    # default class; see repro.remote.policy.SLO_CLASSES).
    remote_slo: str = ""

    def replace(self, **kw: Any) -> "ClientConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class MeshConfig:
    shape: tuple = (16, 16)
    axes: tuple = ("data", "model")

    @property
    def n_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n
