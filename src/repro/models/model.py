"""The decoder-only LM: embed -> scan(superblocks) [+ tail] -> norm -> head.

Covers 9 of the 10 assigned architectures (whisper-base is enc-dec; see
``whisper.py``).  The repeating ``block_pattern`` is expanded as
``num_layers = R * P + tail``: the R full repetitions are *stacked* (leading
dim R per parameter leaf) and executed with ``jax.lax.scan`` — one compiled
superblock body regardless of depth — while the tail layers run unstacked.

Four entry points, one per serving/training phase:

    forward(params, batch)                     -> logits [B, S, V]   (train)
    prefill(params, batch, s_alloc)            -> (last logits, states)
    extend(params, batch, states, q_offset)    -> (last logits, states)
    decode_step(params, tokens, states, pos)   -> (logits [B, V], states)

``extend`` is the task-cascade primitive: document fraction f_j -> f_i reuse
(the KV prefix for [0, q_offset) is already in ``states``).

VLM (qwen2-vl) inputs may carry ``patch_emb`` [B, S_img, D] — the stubbed
vision frontend — which is prepended to the text token embeddings, and
``positions3`` [B, S, 3] for M-RoPE.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..config import ATTN_FULL, ATTN_LOCAL, MAMBA2, ResolvedConfig
from ..distributed.sharding import batch_pspec, constrain
from . import blocks
from .attention import chunk_positions
from .layers import embed_apply, init_embed, init_rmsnorm, lm_head_apply, \
    rmsnorm_apply, spec_embed, spec_rmsnorm
from .runtime import Runtime


def _stack_init(rng, n: int, init_fn):
    """Initialize ``n`` copies of a module, stacked on the leading dim."""
    return jax.vmap(init_fn)(jax.random.split(rng, n))


@dataclass(frozen=True)
class LM:
    rcfg: ResolvedConfig
    rt: Runtime

    # ------------------------------------------------------------------ meta
    @property
    def pattern(self) -> Tuple[str, ...]:
        return self.rcfg.base.block_pattern

    @property
    def n_rep(self) -> int:
        return self.rcfg.base.num_layers // len(self.pattern)

    @property
    def tail_kinds(self) -> Tuple[str, ...]:
        kinds = self.rcfg.base.layer_kinds()
        return kinds[self.n_rep * len(self.pattern):]

    @property
    def dtype(self):
        return jnp.bfloat16 if self.rcfg.base.dtype == "bfloat16" else jnp.float32

    # ---------------------------------------------------------------- params
    def init(self, rng) -> Dict[str, Any]:
        b = self.rcfg.base
        k_emb, k_stage, k_tail = jax.random.split(rng, 3)
        stages = tuple(
            _stack_init(
                jax.random.fold_in(k_stage, pi), self.n_rep,
                functools.partial(
                    blocks.init_block, rcfg=self.rcfg, kind=kind,
                    dtype=self.dtype))
            for pi, kind in enumerate(self.pattern))
        tail = tuple(
            blocks.init_block(jax.random.fold_in(k_tail, ti), self.rcfg,
                              kind, self.dtype)
            for ti, kind in enumerate(self.tail_kinds))
        return {
            "embed": init_embed(k_emb, self.rcfg.padded_vocab, b.d_model,
                                self.dtype),
            "final_norm": init_rmsnorm(b.d_model),
            "stages": stages,
            "tail": tail,
        }

    def param_specs(self) -> Dict[str, Any]:
        stages = tuple(
            jax.tree.map(
                lambda t: (None,) + t,                 # leading R dim replicated
                blocks.spec_block(self.rcfg, kind),
                is_leaf=lambda x: isinstance(x, tuple) and len(x) > 0
                and all(isinstance(a, (str, type(None))) for a in x))
            for kind in self.pattern)
        tail = tuple(blocks.spec_block(self.rcfg, kind)
                     for kind in self.tail_kinds)
        return {
            "embed": spec_embed(),
            "final_norm": spec_rmsnorm(),
            "stages": stages,
            "tail": tail,
        }

    # ---------------------------------------------------------------- states
    def init_states(self, batch: int, s_alloc: int, kv_dtype=None):
        stages = tuple(
            jax.tree.map(
                lambda l: jnp.broadcast_to(l[None], (self.n_rep,) + l.shape),
                blocks.init_block_state(self.rcfg, kind, batch, s_alloc,
                                        self.dtype, kv_dtype=kv_dtype))
            for kind in self.pattern)
        tail = tuple(
            blocks.init_block_state(self.rcfg, kind, batch, s_alloc, self.dtype,
                                    kv_dtype=kv_dtype)
            for kind in self.tail_kinds)
        return {"stages": stages, "tail": tail}

    def state_shapes(self, batch: int, s_alloc: int, kv_dtype=None):
        stages = tuple(
            jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((self.n_rep,) + s.shape, s.dtype),
                blocks.block_state_shape(self.rcfg, kind, batch, s_alloc,
                                         self.dtype, kv_dtype=kv_dtype))
            for kind in self.pattern)
        tail = tuple(
            blocks.block_state_shape(self.rcfg, kind, batch, s_alloc,
                                     self.dtype, kv_dtype=kv_dtype)
            for kind in self.tail_kinds)
        return {"stages": stages, "tail": tail}

    # ------------------------------------------------------- arena state API
    # State pytrees are batched per sequence; the batch axis is 0 for every
    # leaf except scan-stacked "stages" leaves, which carry the repetition
    # dim first (R, B, ...).  ``take_states``/``put_states`` gather/scatter
    # sub-batches along that axis, which is how the serving engine's slot
    # arena packs survivors without per-document Python loops.

    @staticmethod
    def _state_batch_axis(path) -> int:
        key = str(getattr(path[0], "key", getattr(path[0], "idx", path[0])))
        return 1 if key == "stages" else 0

    def take_states(self, states, idx: jnp.ndarray):
        """Gather per-sequence states at ``idx`` [B'] -> batch-B' pytree."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(states)
        out = [jnp.take(leaf, idx, axis=self._state_batch_axis(path))
               for path, leaf in flat]
        return jax.tree_util.tree_unflatten(treedef, out)

    def put_states(self, arena, idx: jnp.ndarray, states):
        """Scatter a batch-B' state pytree into arena slots ``idx``.

        Duplicate slot ids are permitted (used for scratch-slot padding);
        which duplicate wins is unspecified.
        """
        flat_a, treedef = jax.tree_util.tree_flatten_with_path(arena)
        flat_s = jax.tree.leaves(states)
        out = []
        for (path, leaf), sub in zip(flat_a, flat_s):
            if self._state_batch_axis(path) == 0:
                out.append(leaf.at[idx].set(sub.astype(leaf.dtype)))
            else:
                out.append(leaf.at[:, idx].set(sub.astype(leaf.dtype)))
        return jax.tree_util.tree_unflatten(treedef, out)

    @property
    def supports_paged_kv(self) -> bool:
        """True when the slot arena can be addressed IN PLACE (``slots=``
        on ``extend``/``decode_step``): every layer's serve-state is a
        full-attention KV cache, read by the paged kernels, or a Mamba-2
        state, taken and put by slot (O(1) a row).  Sliding-window ring
        caches and the xLSTM/RG-LRU states still require the
        gather/scatter path."""
        return all(k in (ATTN_FULL, MAMBA2)
                   for k in self.rcfg.base.layer_kinds())

    def _entries(self, states):
        """(kind, path, entry) of each layer entry of a state pytree:
        ``("stages", i)`` entries carry the repetition axis first."""
        for i, kind in enumerate(self.pattern):
            yield kind, ("stages", i), states["stages"][i]
        for i, kind in enumerate(self.tail_kinds):
            yield kind, ("tail", i), states["tail"][i]

    def _map_entries(self, states, fn):
        """``states`` with each layer entry replaced by ``fn(kind, path,
        entry)``."""
        out = {"stages": list(states["stages"]), "tail": list(states["tail"])}
        for kind, path, entry in self._entries(states):
            out[path[0]][path[1]] = fn(kind, path, entry)
        return {"stages": tuple(out["stages"]), "tail": tuple(out["tail"])}

    def state_nbytes(self, states) -> Dict[str, int]:
        """Bytes of a state pytree (arrays or shapes) by kind: ``kv``
        (attention caches, which grow with the sequence) and
        ``recurrent`` (fixed-size states)."""
        out = {"kv": 0, "recurrent": 0}
        for kind, _, entry in self._entries(states):
            key = "kv" if kind in (ATTN_FULL, ATTN_LOCAL) else "recurrent"
            out[key] += sum(int(math.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
                            for l in jax.tree.leaves(entry))
        return out

    def _kv_window_idx(self, slots: jnp.ndarray, start: jnp.ndarray,
                       length: int):
        return slots[:, None], chunk_positions(start, length)  # [B,1],[B,L]

    def take_kv_window(self, states, slots: jnp.ndarray,
                       start: jnp.ndarray, length: int):
        """Gather cache rows [start[b], start[b]+length) of every KV leaf
        at arena rows ``slots`` -> a tiny [B, length, KV, Dh]-per-leaf
        pytree (None for a recurrent layer).  With ``put_kv_window`` this
        is the paged op-suffix UNDO LOG: the serving engine snapshots the
        ``length`` cache positions an operation suffix will dirty, runs it
        in place, then restores — O(B * op_len) bytes instead of the full
        [B, S] row copy.  Recurrent states need no entry: the op suffix
        does not write them (``extend(commit_recurrent=False)``).  Only
        valid for ``supports_paged_kv`` models."""
        si, win = self._kv_window_idx(slots, start, length)

        def take(kind, path, entry):
            if kind != ATTN_FULL:
                return None
            return jax.tree.map(lambda l: (l[:, si, win] if path[0] ==
                                           "stages" else l[si, win]), entry)
        return self._map_entries(states, take)

    def put_kv_window(self, states, slots: jnp.ndarray,
                      start: jnp.ndarray, length: int, window):
        """Scatter a ``take_kv_window`` snapshot back into the arena.
        Duplicate rows (scratch-slot padding) are permitted; which
        duplicate wins is unspecified — scratch contents are never read
        unmasked."""
        si, win = self._kv_window_idx(slots, start, length)
        saved = {path: entry for _, path, entry in self._entries(window)}

        def put(kind, path, entry):
            if kind != ATTN_FULL:
                return entry
            return jax.tree.map(
                lambda l, w: (l.at[:, si, win].set(w) if path[0] == "stages"
                              else l.at[si, win].set(w)), entry, saved[path])
        return self._map_entries(states, put)

    def state_specs(self, *, batch_sharded: bool, seq_sharded: bool):
        def with_lead(tree):
            return jax.tree.map(
                lambda t: (None,) + t, tree,
                is_leaf=lambda x: isinstance(x, tuple) and len(x) > 0
                and all(isinstance(a, (str, type(None))) for a in x))
        stages = tuple(
            with_lead(blocks.spec_block_state(
                self.rcfg, kind, batch_sharded=batch_sharded,
                seq_sharded=seq_sharded))
            for kind in self.pattern)
        tail = tuple(
            blocks.spec_block_state(self.rcfg, kind,
                                    batch_sharded=batch_sharded,
                                    seq_sharded=seq_sharded)
            for kind in self.tail_kinds)
        return {"stages": stages, "tail": tail}

    # ----------------------------------------------------------------- embed
    def _dp_spec(self):
        if self.rt.mesh is None:
            return None
        return batch_pspec(self.rt.mesh, None, None)

    def _constrain_act(self, x):
        if self.rt.mesh is None:
            return x
        mesh = self.rt.mesh
        seq = "model" if (self.rt.sp_activations
                          and x.shape[1] % mesh.shape["model"] == 0) else None
        dp = batch_pspec(mesh)[0] if x.shape[0] % _dp_size(mesh) == 0 else None
        return constrain(x, mesh, P(dp, seq, None))

    def embed_inputs(self, params, batch: Dict[str, jnp.ndarray]):
        b = self.rcfg.base
        x = embed_apply(params["embed"], batch["tokens"]).astype(self.dtype)
        if b.frontend_stub == "vision_patches" and "patch_emb" in batch:
            x = jnp.concatenate(
                [batch["patch_emb"].astype(self.dtype), x], axis=1)
        if b.frontend_stub == "audio_frames" and "frame_emb" in batch:
            x = jnp.concatenate(
                [batch["frame_emb"].astype(self.dtype), x], axis=1)
        return self._scale_embed(x)

    def _scale_embed(self, x):
        b = self.rcfg.base
        if getattr(b, "embed_scale", False):
            x = x * jnp.asarray(b.d_model ** 0.5, self.dtype)
        if b.embedding_multiplier != 1.0:
            x = x * jnp.asarray(b.embedding_multiplier, self.dtype)
        return x

    def _head(self, params, x):
        """Final norm and tied head: logits [..., V] (float32)."""
        b = self.rcfg.base
        x = rmsnorm_apply(params["final_norm"], x, b.norm_eps)
        logits = lm_head_apply(params["embed"], x, b.logit_softcap)
        if b.logits_scaling != 1.0:
            logits = logits / b.logits_scaling
        return logits

    # ------------------------------------------------------------------ core
    def _run_blocks(self, params, x, *, mode, states=None, cache_len=None,
                    q_offset=0, kv_len=None, q_start=None, slots=None,
                    block_tables=None, positions=None, positions3=None,
                    commit_recurrent=True):
        rcfg, rt = self.rcfg, self.rt
        dp_spec = self._dp_spec()
        pattern = self.pattern
        aux0 = jnp.zeros((), jnp.float32)

        def superblock(carry, xs):
            x, aux = carry
            stage_params, stage_states = xs
            new_states = []
            for pi, kind in enumerate(pattern):
                st = None if stage_states is None else stage_states[pi]
                x, ns, a = blocks.block_apply(
                    stage_params[pi], x, kind=kind, rcfg=rcfg, rt=rt,
                    mode=mode, state=st, cache_len=cache_len,
                    q_offset=q_offset, kv_len=kv_len, q_start=q_start,
                    slots=slots, block_tables=block_tables,
                    positions=positions, positions3=positions3,
                    dp_spec=dp_spec, commit_recurrent=commit_recurrent)
                x = self._constrain_act(x)
                new_states.append(ns)
                aux = aux + a
            return (x, aux), (tuple(new_states) if mode != "train" else 0)

        if mode == "train" and rt.remat:
            superblock = jax.checkpoint(
                superblock,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

        if self.n_rep > 0 and rt.unroll_layers:
            # Python-loop unroll (dry-run cost-extrapolation compiles)
            carry = (x, aux0)
            new_list = []
            for r in range(self.n_rep):
                sp = jax.tree.map(lambda l: l[r], params["stages"])
                st = (jax.tree.map(lambda l: l[r], states["stages"])
                      if states is not None else None)
                carry, ns = superblock(carry, (sp, st))
                new_list.append(ns)
            x, aux = carry
            if states is not None:
                new_stage_states = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *new_list)
            else:
                new_stage_states = ()
        elif self.n_rep > 0:
            st_stages = states["stages"] if states is not None else tuple(
                None for _ in pattern)
            if states is None:
                # scan still needs xs leaves of leading dim R; use params only
                (x, aux), _ = jax.lax.scan(
                    lambda c, sp: superblock(c, (sp, None)),
                    (x, aux0), params["stages"])
            else:
                (x, aux), new_stage_states = jax.lax.scan(
                    superblock, (x, aux0), (params["stages"], st_stages))
        else:
            aux = aux0
            new_stage_states = ()

        new_tail = []
        for ti, kind in enumerate(self.tail_kinds):
            st = None if states is None else states["tail"][ti]
            x, ns, a = blocks.block_apply(
                params["tail"][ti], x, kind=kind, rcfg=rcfg, rt=rt,
                mode=mode, state=st, cache_len=cache_len, q_offset=q_offset,
                kv_len=kv_len, q_start=q_start, slots=slots,
                block_tables=block_tables,
                positions=positions, positions3=positions3, dp_spec=dp_spec,
                commit_recurrent=commit_recurrent)
            x = self._constrain_act(x)
            new_tail.append(ns)
            aux = aux + a

        if mode == "train":
            return x, None, aux
        if states is None:
            new_stage_states = tuple(
                None for _ in pattern) if self.n_rep else ()
        return x, {"stages": new_stage_states, "tail": tuple(new_tail)}, aux

    # ------------------------------------------------------------ entry pts
    def forward(self, params, batch: Dict[str, jnp.ndarray]):
        """Training/eval forward -> (logits [B, S, V], moe aux)."""
        x = self.embed_inputs(params, batch)
        B, S, _ = x.shape
        positions = batch.get(
            "positions", jnp.broadcast_to(jnp.arange(S)[None], (B, S)))
        x, _, aux = self._run_blocks(
            params, x, mode="train", positions=positions,
            positions3=batch.get("positions3"))
        return self._head(params, x), aux

    def loss(self, params, batch: Dict[str, jnp.ndarray]):
        """Mean next-token xent (+ MoE aux).  ``labels`` [B, S_total]."""
        logits, aux = self.forward(params, batch)
        labels = batch["labels"]
        V = logits.shape[-1]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        onehot = jax.nn.one_hot(labels, V, dtype=jnp.float32)
        tok_ll = jnp.sum(onehot * logp, axis=-1)
        mask = batch.get("loss_mask", jnp.ones_like(tok_ll))
        loss = -jnp.sum(tok_ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        return loss + 0.01 * aux

    def prefill(self, params, batch: Dict[str, jnp.ndarray], *,
                s_alloc: Optional[int] = None):
        """Full prompt pass -> (last-token logits [B, V], states)."""
        x = self.embed_inputs(params, batch)
        B, S, _ = x.shape
        positions = batch.get(
            "positions", jnp.broadcast_to(jnp.arange(S)[None], (B, S)))
        states = self.init_states(B, s_alloc or S) if s_alloc else None
        if states is not None:
            # prefill writes into preallocated caches via extend at offset 0
            x, new_states, _ = self._run_blocks(
                params, x, mode="extend", states=states, q_offset=0,
                positions=positions, positions3=batch.get("positions3"),
                cache_len=jnp.zeros((B,), jnp.int32))
        else:
            x, new_states, _ = self._run_blocks(
                params, x, mode="prefill", positions=positions,
                positions3=batch.get("positions3"))
        return self._head(params, x[:, -1:])[:, 0], new_states

    def extend(self, params, batch: Dict[str, jnp.ndarray], states,
               q_offset: int, kv_len: Optional[jnp.ndarray] = None,
               slots: Optional[jnp.ndarray] = None,
               block_tables: Optional[jnp.ndarray] = None,
               q_start: Optional[jnp.ndarray] = None,
               commit_recurrent: bool = True):
        """Cascade fraction-extension: new tokens at [q_offset, q_offset+S).

        ``kv_len`` [B] is the TRUE (unpadded) sequence length including this
        chunk: keys at positions >= kv_len[b] are bucket PAD and masked for
        every query, so padded rows cannot attend to PAD KV written by
        earlier chunks (the serving engine passes per-document true lengths;
        None keeps the unmasked fast path for exact-length callers).

        ``slots`` [B] switches to PAGED mode: ``states`` is the slot arena
        (batch dim = arena rows) and row ``slots[b]`` is extended in place
        — the chunk's KV scatters into the arena and attention reads it
        through the paged kernels, so no per-launch row gather/scatter is
        needed.  Requires ``supports_paged_kv``.

        ``block_tables`` [B, nblocks] (paged mode only) redirects READS:
        cache block ``j`` of sequence ``b`` is fetched from arena row
        ``block_tables[b, j]`` instead of ``slots[b]`` — the prefix-sharing
        indirection.  Writes still land in row ``slots[b]``.

        ``q_start`` [B] (traced; ``supports_paged_kv`` models) gives each
        row its own start: row ``b``'s chunk takes positions [q_start[b],
        q_start[b]+S), its KV is written there and ``kv_len`` defaults to
        ``q_start + S``.  ``q_offset`` then bounds every start from above
        and fixes the keys attended, [0, q_offset + S).  An unpadded
        chunk's last position is then every row's true last token, so the
        returned logits are each row's own — the serving engine runs a
        whole operation suffix this way in one pass.

        Recurrent (Mamba-2) layers resume each row from the state
        ``states`` holds for it, masked to ``kv_len``; an extend at
        ``q_offset`` 0 (no ``q_start``) starts from the initial state.
        ``commit_recurrent`` False returns their states as they came in.
        """
        x = self.embed_inputs(params, batch)
        B, S, _ = x.shape
        positions = q_offset + jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        if q_start is not None:
            assert self.supports_paged_kv, \
                "ragged-start extend needs full-attention or Mamba-2 layers"
            positions = chunk_positions(q_start, S)
            if kv_len is None:
                kv_len = q_start + S
        x, new_states, _ = self._run_blocks(
            params, x, mode="extend", states=states, q_offset=q_offset,
            kv_len=kv_len, q_start=q_start, slots=slots,
            block_tables=block_tables, positions=positions,
            positions3=batch.get("positions3"),
            cache_len=jnp.full((B,), q_offset, jnp.int32),
            commit_recurrent=commit_recurrent)
        return self._head(params, x[:, -1:])[:, 0], new_states

    def decode_step(self, params, tokens: jnp.ndarray, states,
                    pos: jnp.ndarray, slots: Optional[jnp.ndarray] = None,
                    block_tables: Optional[jnp.ndarray] = None):
        """One decode step. tokens [B], pos [B] -> (logits [B, V], states).

        ``slots`` [B] switches to PAGED mode: ``states`` is the slot arena
        and the step reads/writes row ``slots[b]`` in place (the token's
        KV lands at position ``pos[b]`` of that row; callers that must not
        dirty the row — the serving readout — bracket the steps with
        ``take_kv_window``/``put_kv_window``).  ``block_tables``
        [B, nblocks] redirects cache READS per block (prefix sharing);
        the written token still lands in ``slots[b]``."""
        x = self._scale_embed(
            embed_apply(params["embed"], tokens[:, None]).astype(self.dtype))
        positions = pos[:, None]
        positions3 = None
        if self.rcfg.base.mrope_sections is not None:
            positions3 = jnp.broadcast_to(
                pos[:, None, None], (pos.shape[0], 1, 3)).astype(jnp.int32)
        x, new_states, _ = self._run_blocks(
            params, x, mode="decode", states=states, cache_len=pos,
            slots=slots, block_tables=block_tables, positions=positions,
            positions3=positions3)
        return self._head(params, x)[:, 0], new_states


def _dp_size(mesh) -> int:
    n = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return n
