"""Multi-chip tensor-parallel serving replica: one engine, one mesh,
ONE compiled step.

A single-host serving replica's hard ceiling is one chip's HBM: the
full weight set plus every resident's paged KV must fit one device.
`ServingEngine(mesh=...)` / `PADDLE_TPU_MESH=dpXmpY` makes ONE replica
span a `(dp, mp)` device mesh while staying ONE compiled program — the
unified ragged step is sharded with GSPMD, not rewritten:

- the per-layer paged KV pools `[num_pages, page_size, H_kv, D]` (and
  the int8 lane's rowwise scale pools) shard over their KV-HEAD axis:
  every chip holds a 1/mp slice of EVERY page, so the per-chip HBM
  cost of a resident token drops by mp and the same per-chip page
  budget admits ~mp x the residents;
- the attention input projections (q/k/v_proj, GPT's fused qkv_proj)
  shard over their head-grouped OUTPUT dim (column-parallel — each
  chip computes whole heads' queries/keys/values with the full
  contraction, bit-exactly the columns the unsharded matmul produces);
- page tables, `pos`/`q_len`, the grouped-walk operands, sampling
  vectors, held logits — and the scheduler, radix prefix cache,
  preemption and spec-decode machinery that feed them — stay
  REPLICATED and completely unchanged: sharding is pure data-plane.

The ragged paged-attention walk treats `kv_head` as an independent
axis (the batch dimension of the Pallas kernel's dots; on a mesh the
kernel runs per device over its own heads — GSPMD cannot partition a
Mosaic kernel, see `ops/pallas.kernel_mesh`), so each chip's page
walk needs NO cross-chip traffic: scatter writes land
on the chip that owns the head slice, each shard's online softmax
folds only its own heads, and the one place shards meet is the
attention OUTPUT — `DecodeCache.out_shard` constrains it back to
replicated, which GSPMD materializes as a single ALL-GATHER per layer.
All-gathers are pure data movement (concatenation), never partial-sum
all-reduces, so the fp math is NEVER reassociated — which is what
makes an mp>1 engine bit-token-identical to the mp=1 oracle, the same
provable-identity discipline every other engine gate holds to
(`collective_counts()` pins it: zero all-reduce, one output
all-gather per layer).

The `dp` axis is accepted and validated for mesh-geometry parity with
the training stack (fleet topology); this replica replicates over it
— slot-axis dp sharding and the real-chip multi-host measurement are
the named follow-ups (ROADMAP). CPU tier-1 proves the whole thing on
8 virtual devices (`xla_force_host_platform_device_count`, the
tests/test_distributed.py pattern): the mesh, the shardings, the
collectives and the token-identity oracle are all real; only the HBM
bandwidth win is modeled (`count_page_block_reads`), as with every
other kernel claim in this repo.
"""
from __future__ import annotations

import os
import re
from typing import List, Optional

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ServingTP", "resolve_serving_mesh", "parse_mesh_spec",
           "collective_counts"]

# env spec: "dp2mp4" (also accepted with an explicit separator,
# "dp2xmp4"); "off"/"" = single-device serving, the default
_MESH_RE = re.compile(r"^dp(\d+)x?mp(\d+)$")

# parameter-name fragments marking the attention input projections —
# the weights that shard over mp (column-parallel over whole heads).
# Everything else (o_proj/out_proj, MLP, embeddings, norms, lm_head)
# stays replicated ON PURPOSE: row-parallel output projections would
# make GSPMD sum PARTIAL products with an all-reduce, reassociating
# the fp reduction and breaking the bit-token-identity oracle. The
# replicated output side is the documented trade for a provable mp
# gate (README "Multi-chip serving").
_QKV_MARKERS = ("q_proj.", "k_proj.", "v_proj.", "qkv_proj.")


def parse_mesh_spec(spec: str):
    """'dpXmpY' -> (dp, mp); raises ValueError on anything else."""
    m = _MESH_RE.match(spec.strip().lower())
    if m is None:
        raise ValueError(
            f"mesh spec must look like 'dp2mp4' "
            f"(PADDLE_TPU_MESH / ServingEngine(mesh=...)), got "
            f"{spec!r}")
    dp, mp = int(m.group(1)), int(m.group(2))
    if dp < 1 or mp < 1:
        raise ValueError(
            f"mesh axes must be >= 1, got dp={dp} mp={mp}")
    return dp, mp


class ServingTP:
    """The engine's tensor-parallel state: a `(dp, mp)` jax Mesh over
    the first dp*mp visible devices plus the shardings every engine
    array gets placed with. Built once at engine construction; the
    compiled programs keep the mesh they were traced with."""

    def __init__(self, dp: int, mp: int, devices=None):
        self.dp, self.mp = int(dp), int(mp)
        n = self.dp * self.mp
        devs = list(jax.devices() if devices is None else devices)
        if n > len(devs):
            raise ValueError(
                f"serving mesh dp{self.dp}xmp{self.mp} needs {n} "
                f"devices but only {len(devs)} are visible; shrink "
                f"the mesh or provision more chips "
                f"(CPU simulation: xla_force_host_platform_"
                f"device_count)")
        self.mesh = Mesh(np.asarray(devs[:n]).reshape(self.dp, self.mp),
                         ("dp", "mp"))
        # replicated: page tables, pos/q_len/group operands, sampling
        # vectors, held logits, every non-QKV weight — the control
        # plane never shards
        self.rep = NamedSharding(self.mesh, P())
        # paged KV pools [num_pages, page_size, H_kv, D] and the int8
        # lane's scale pools [num_pages, page_size, H_kv]: shard the
        # KV-HEAD axis — each chip owns a 1/mp slice of EVERY page
        # (no trailing None: it is the spelling the compiled step's
        # outputs come back with, so the pools a step returns hit the
        # same jit cache entry as the pools it was first given)
        self.pool_shard = NamedSharding(self.mesh, P(None, None, "mp"))
        self.scale_shard = NamedSharding(self.mesh, P(None, None, "mp"))
        self._col = NamedSharding(self.mesh, P(None, "mp"))
        self._vec = NamedSharding(self.mesh, P("mp"))

    @property
    def shape(self) -> str:
        return f"dp{self.dp}xmp{self.mp}"

    @property
    def size(self) -> int:
        return self.dp * self.mp

    def __repr__(self):
        return f"ServingTP({self.shape})"

    # -- construction-time geometry validation -----------------------------
    def validate_geometry(self, *, n_kv: int, n_heads: int,
                          hidden: int):
        """Raise a clear ValueError when the model's head geometry
        cannot shard over this mesh's mp degree — BEFORE any array is
        placed (no silent mis-shard). Legal mp values are named in the
        error so the fix is a config edit, not a debugging session."""
        if self.mp <= 1:
            return
        if n_kv % self.mp and n_heads % self.mp:
            bad = f"H_kv={n_kv} and H={n_heads} are"
        elif n_kv % self.mp:
            bad = f"H_kv={n_kv} is"
        elif n_heads % self.mp or hidden % self.mp:
            bad = f"H={n_heads} (hidden={hidden}) is"
        else:
            return
        n_dev = len(jax.devices())
        legal = [m for m in range(1, n_kv + 1)
                 if n_kv % m == 0 and n_heads % m == 0
                 and hidden % m == 0 and m <= n_dev]
        raise ValueError(
            f"serving mesh {self.shape}: {bad} not divisible by "
            f"mp={self.mp} — the paged KV pools shard over the "
            f"kv-head axis and the QKV projections over whole heads, "
            f"so every head count must split evenly across the mp "
            f"shards (model: H_kv={n_kv}, H={n_heads}, "
            f"hidden={hidden}). Legal mp values for this model on "
            f"{n_dev} visible devices: {legal}")

    # -- placement ---------------------------------------------------------
    def place_state(self, model, state_tensors) -> List:
        """Return the engine's weight snapshot placed on the mesh: the
        attention input projections (matched by name against the
        standard q/k/v/qkv_proj layout) shard column-parallel over
        their head-grouped output dim, everything else replicates.
        The MODEL's own tensors are never touched — engines snapshot,
        they do not rebind (tests share one model across engines)."""
        names = {id(p): name for name, p in model.named_parameters()} \
            if hasattr(model, "named_parameters") else {}
        placed = []
        for t in state_tensors:
            v = t._value
            name = names.get(id(t), "")
            if (self.mp > 1
                    and any(mk in name for mk in _QKV_MARKERS)
                    and v.shape[-1] % self.mp == 0):
                sh = self._col if v.ndim == 2 else self._vec
                placed.append(jax.device_put(v, sh))
            else:
                placed.append(jax.device_put(v, self.rep))
        return placed

    def place_pool(self, arr):
        """Place one per-layer K or V pool (kv-head axis sharded)."""
        return jax.device_put(arr, self.pool_shard)

    def place_scale(self, arr):
        """Place one int8 rowwise scale pool (kv-head axis sharded)."""
        return jax.device_put(arr, self.scale_shard)

    def place_adapter_col(self, arr):
        """Place one adapter-pool B tensor [P, R, out] with its
        head-grouped OUTPUT dim sharded over mp — matching the
        column-parallel q/k/v projections its delta adds to (the add
        is shard-local: no collective). Falls back to replicated when
        the out dim does not divide (the engine's geometry validation
        makes that unreachable for q/k/v)."""
        if self.mp > 1 and arr.shape[-1] % self.mp == 0:
            return jax.device_put(
                arr, NamedSharding(self.mesh, P(None, None, "mp")))
        return jax.device_put(arr, self.rep)

    def replicate(self, arr):
        """Place a host/step operand replicated over the whole mesh
        (page tables, pos, tokens, q_len, sampling vectors, ...)."""
        return jax.device_put(arr, self.rep)

    # -- the modeled per-step collective count ------------------------------
    def step_collectives(self, n_layers: int) -> int:
        """Host-side model of the sharded step's collective count —
        the number the flight recorder logs per step and the --tp-ab
        bench pins: exactly ONE output all-gather per layer (the
        attention output returning to replicated), ZERO all-reduces.
        `collective_counts()` verifies the model against the compiled
        HLO."""
        return int(n_layers) if self.mp > 1 else 0


def resolve_serving_mesh(override=None,
                         env: str = "PADDLE_TPU_MESH"
                         ) -> Optional[ServingTP]:
    """The engine's mesh gate. An explicit override wins: None defers
    to the env var, False forces single-device, a ServingTP passes
    through, a 'dpXmpY' string / (dp, mp) tuple / jax Mesh (or
    ProcessMesh) with dp+mp axes builds one. PADDLE_TPU_MESH='' or
    'off' (the default) means single-device serving — every existing
    deployment is untouched. Read at engine construction; the
    compiled programs keep the mesh they were traced with."""
    if override is None:
        spec = os.environ.get(env, "off").strip()
        if spec in ("", "off"):
            return None
        return ServingTP(*parse_mesh_spec(spec))
    if override is False:
        return None
    if isinstance(override, ServingTP):
        return override
    if isinstance(override, str):
        return ServingTP(*parse_mesh_spec(override))
    if isinstance(override, (tuple, list)) and len(override) == 2:
        return ServingTP(int(override[0]), int(override[1]))
    jm = getattr(override, "jax_mesh", override)   # ProcessMesh | Mesh
    if isinstance(jm, Mesh):
        names = list(jm.axis_names)
        if "mp" not in names:
            raise ValueError(
                f"serving mesh needs an 'mp' axis (and optionally "
                f"'dp'); got axes {names}")
        mp = jm.shape["mp"]
        dp = jm.shape.get("dp", jm.size // mp)
        if dp * mp != jm.size:
            raise ValueError(
                f"serving mesh must factor as dp x mp; got axes "
                f"{dict(jm.shape)} over {jm.size} devices")
        return ServingTP(dp, mp, devices=list(jm.devices.flat))
    raise ValueError(
        f"mesh must be None/False, a 'dpXmpY' spec, a (dp, mp) "
        f"tuple, a ServingTP, or a jax Mesh/ProcessMesh with dp/mp "
        f"axes; got {type(override).__name__}")


# HLO op spellings of the collectives GSPMD can insert (async pairs
# count once via their -start form)
_COLL_RE = {
    "all_reduce": re.compile(r"\ball-reduce(?:-start)?\("),
    "all_gather": re.compile(r"\ball-gather(?:-start)?\("),
    "reduce_scatter": re.compile(r"\breduce-scatter\("),
    "all_to_all": re.compile(r"\ball-to-all\("),
    "collective_permute":
        re.compile(r"\bcollective-permute(?:-start)?\("),
}


def collective_counts(compiled_text: str) -> dict:
    """Count the collectives in a compiled HLO module's text — the
    ground truth behind `ServingTP.step_collectives`'s model. The
    serving contract the tests and --tp-ab pin: `all_reduce == 0`
    (no partial-sum reassociation, ever — that is what keeps mp>1
    bit-token-identical) and `all_gather == n_layers` (exactly one
    output collective per layer per step)."""
    return {name: len(rx.findall(compiled_text))
            for name, rx in _COLL_RE.items()}
