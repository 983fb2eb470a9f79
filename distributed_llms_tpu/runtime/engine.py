"""Inference engine: ties config + params + mesh + decode loop together.

Functional successor of the reference's MasterNode inference surface
(initialize_model / run_inference, src/master/node.py:54-138) minus the
socket runtime: model placement is ``device_put`` onto a mesh, inference is a
jit-compiled generate, results are decoded text (the reference returned raw
pickled partials, defect D9).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

import contextlib

from ..core.config import Config, ModelConfig, RuntimeConfig
from ..core.observability import METRICS, get_logger
from ..core import profiling
from ..models import kv_cache, model as model_lib
from ..models.presets import get_preset
from . import generate as gen_lib
from . import shapes as shapes_lib
from .tokenizer import get_tokenizer, pad_batch

log = get_logger("engine")


def _to_host(out) -> np.ndarray:
    """Device->host for generation outputs.  On a mesh spanning multiple
    processes (BASELINE config 5) the output array is not fully addressable
    from any one process; allgather the tiles first (every process then
    holds — and returns — the same full batch)."""
    out = jax.block_until_ready(out)
    if not getattr(out, "is_fully_addressable", True):
        from jax.experimental import multihost_utils

        out = multihost_utils.process_allgather(out, tiled=True)
    return np.asarray(out)


@dataclass
class GenerationResult:
    text: list[str]
    tokens: np.ndarray  # [B, N]
    prompt_tokens: int
    generated_tokens: int
    seconds: float

    @property
    def tokens_per_second(self) -> float:
        return self.generated_tokens / max(self.seconds, 1e-9)


def _parallel_model(cfg: ModelConfig, rt: RuntimeConfig, mesh_cfg: Any):
    """The ParallelModel for a multi-device ``MeshConfig``, else None."""
    if mesh_cfg is None or mesh_cfg.num_devices <= 1:
        return None
    from ..parallel.api import make_parallel_model

    return make_parallel_model(
        cfg, mesh_cfg,
        num_microbatches=max(rt.microbatches, 1),
        kv_dtype=rt.kv_cache_dtype,
    )


class InferenceEngine:
    """Inference engine, single-device or mesh-parallel.

    `params` may come from the checkpoint converter (real weights) or
    ``init_params`` (random, for benchmarks) — the engine is agnostic.
    With ``parallel`` (a parallel.api.ParallelModel) the params are placed
    onto the mesh (``device_put`` per NamedSharding — the reference's
    "distribute" without tensor bytes on a socket) and generation runs the
    pipelined / tensor-parallel forward.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        rt: RuntimeConfig,
        params: Any,
        tokenizer=None,
        parallel: Any = None,  # parallel.api.ParallelModel
    ) -> None:
        self.cfg = cfg
        self.rt = rt
        self.parallel = parallel
        self.tokenizer = tokenizer or get_tokenizer(None)
        kv_cache.refuse_unpaged_state(
            cfg, mesh=parallel is not None, speculative=rt.spec_decode)
        # Out-of-vocab ids silently become NaN embeddings (jnp.take fills
        # OOB gathers) — reject the mismatch loudly instead.
        tok_vocab = getattr(self.tokenizer, "vocab_size", None)
        if tok_vocab is not None and tok_vocab > cfg.vocab_size:
            raise ValueError(
                f"tokenizer vocab ({tok_vocab}, incl. specials) exceeds model "
                f"vocab ({cfg.vocab_size}); token ids would be out of range"
            )
        if parallel is not None:
            self.params = parallel.shard_params(params)
            self._forward_fn = parallel.as_forward_fn()
            self._make_cache = parallel.as_make_cache()
            self._decode_fn = parallel.as_decode_fn()  # fused pipelined decode
        else:
            self.params = params
            self._forward_fn = None  # generate_tokens' single-device default
            self._decode_fn = None
            # KV-cache dtype knob: bound once so the jitted decode sees a
            # stable (identity-hashed) make_cache and caches the compilation.
            kv_dtype = jnp.dtype(rt.kv_cache_dtype)
            self._make_cache = lambda cfg_, b, s, prompt_len=None: kv_cache.init_cache(
                cfg_, b, s, dtype=kv_dtype
            )
        if rt.spec_decode:
            # CONFIG-DRIVEN knob policy (same as runtime.paged_pages on a
            # mesh engine): one shared cluster config with spec_decode on
            # must never brick a worker whose engine can't self-speculate —
            # mesh engines and quantized-store engines DEGRADE to plain
            # serving with a loud warning (an explicit
            # attach_draft(draft_cfg, draft_params) still works on the
            # latter).  Genuinely malformed configs still raise.
            if cfg.ragged_decode:
                # speculative_generate_tokens rejects ragged_decode (the
                # prefix-read kernel cannot serve its masks); surface the
                # conflict at construction, not on the first request.
                raise ValueError(
                    "runtime.spec_decode is incompatible with "
                    "model.ragged_decode; unset one"
                )
            if rt.spec_k < 1:
                # Fail at construction, not on the first routed request.
                raise ValueError(f"runtime.spec_k must be >= 1, got {rt.spec_k}")
            if parallel is not None:
                log.warning(
                    "runtime.spec_decode is single-device; this mesh engine "
                    "serves PLAIN (generate_text / continuous_batcher keep "
                    "working, just without speculation)"
                )
            elif self._serves_quantized():
                log.warning(
                    "spec_decode requested but the engine serves quantized "
                    "weights; serving PLAIN (no self-draft to quantize). "
                    "Attach an explicit draft for speculative serving."
                )
            else:
                # Self-speculation: the draft is this engine's own blocks
                # weight-only quantized.
                self.attach_draft(quantize_bits=rt.spec_draft_quantize)
        # Session store: caches persist across turns; with kv_host_spill only
        # the most recent max_resident_sessions stay in device memory.
        from .session import SessionManager

        self.sessions = SessionManager(
            max_resident=rt.max_resident_sessions if rt.kv_host_spill else (1 << 30)
        )

    @classmethod
    def from_preset(
        cls, name: str, rt: RuntimeConfig | None = None, rng_seed: int = 0,
        mesh_cfg: Any = None,  # core.config.MeshConfig
        quantization: str | None = None,  # "int8" | "int4" block weights,
        #   required (and only used) under rt.serve_quantized
        **overrides,
    ) -> "InferenceEngine":
        """Random weights from a seed at a preset's shapes, optionally
        mesh-parallel like :meth:`from_store`.  With ``rt.serve_quantized``
        the block weights are generated quantized, leaf by leaf and already
        sharded (models.model.init_params_quantized) — the only way a
        full-width 7B preset fits one chip."""
        cfg = get_preset(name, **overrides)
        rt = rt or RuntimeConfig()
        parallel = _parallel_model(cfg, rt, mesh_cfg)
        key = jax.random.key(rng_seed)
        if rt.serve_quantized:
            bits = {"int8": 8, "int4": 4}.get(quantization)
            if bits is None:
                raise ValueError(
                    "serve_quantized=True on a preset needs "
                    "checkpoint.quantization='int8'|'int4', got "
                    f"{quantization!r}"
                )
            params = model_lib.init_params_quantized(
                key, cfg, bits, mesh=parallel.mesh if parallel else None
            )
        else:
            params = model_lib.init_params(key, cfg)
        return cls(cfg, rt, params, parallel=parallel)

    @classmethod
    def from_store(
        cls,
        store_dir: str,
        rt: RuntimeConfig | None = None,
        mesh_cfg: Any = None,  # core.config.MeshConfig
        tokenizer=None,
    ) -> "InferenceEngine":
        """Build from a shard store, optionally mesh-parallel.

        This is the product path the reference promised (split one model
        across workers, src/master/node.py:84-115) done TPU-native: the mesh
        comes from ``Config.mesh``, microbatches from
        ``RuntimeConfig.microbatches``, placement is ``device_put``.
        """
        from ..checkpoint import store as store_lib
        from ..core.config import ModelConfig

        rt = rt or RuntimeConfig()
        manifest = store_lib.load_manifest(store_dir)
        if manifest.get("model_config") is None:
            raise ValueError(f"store {store_dir} has no embedded model_config")
        cfg = ModelConfig(**manifest["model_config"])
        if tokenizer is None:
            # The store records the model's own tokenizer (save_shards copies
            # the HF files in — the reference's master-side HF tokenizer,
            # src/master/node.py:235-245).  Serving a real checkpoint through
            # byte-level ids produces gibberish; warn loudly if that is about
            # to happen.
            import os

            from .tokenizer import ByteTokenizer

            tok_rel = manifest.get("tokenizer")
            if tok_rel:
                tokenizer = get_tokenizer(os.path.join(store_dir, tok_rel))
            if tokenizer is None or isinstance(tokenizer, ByteTokenizer):
                if cfg.vocab_size > ByteTokenizer.vocab_size:
                    log.warning(
                        "store %s has no usable tokenizer (manifest tokenizer=%r) "
                        "but the model vocab is %d; falling back to byte-level "
                        "ids — decoded text will be wrong for a real checkpoint. "
                        "Re-save the store with tokenizer_src=<checkpoint dir>.",
                        store_dir, tok_rel, cfg.vocab_size,
                    )
        if rt.serve_quantized:
            # Weight-only quantized serving: decoder-block weights stay
            # int8/int4 in HBM; the block scan hands layers._contract each
            # QuantizedTensor stack whole with the layer's index, and the
            # fused dequant-matmul Pallas kernel reads the layer's tiles
            # where they lie on TPU (ops/quant_matmul.py); dequantize+einsum
            # on the layer's slice elsewhere.  Embedding/unembedding tables are rehydrated —
            # gathers can't consume QuantizedTensor leaves.
            if not manifest.get("quantization"):
                raise ValueError(
                    f"serve_quantized=True but store {store_dir} is not "
                    "quantized; save it with quantization='int8'|'int4'"
                )
            from ..checkpoint import quantize as quant_lib

            params = store_lib.load_shards(store_dir, dequantize=False)
            params = {
                k: (v if k == "blocks" else quant_lib.dequantize_tree(v, cfg.dtype))
                for k, v in params.items()
            }
        else:
            params = store_lib.reconstruct(store_dir, dtype=cfg.dtype)
        return cls(cfg, rt, params, tokenizer=tokenizer,
                   parallel=_parallel_model(cfg, rt, mesh_cfg))

    def _batch_multiple(self) -> int:
        """Batch rows must divide evenly over the data axis, times the
        microbatch count when the pipeline schedule splits the batch."""
        if self.parallel is None:
            return 1
        data = self.parallel.mesh.shape.get("data", 1)
        mb = self.parallel.num_microbatches if self.parallel.pipelined else 1
        return max(mb, 1) * data

    def generate_text(
        self, prompts: list[str], max_new_tokens: int | None = None, seed: int | None = None
    ) -> GenerationResult:
        kv_cache.refuse_unpaged_state(self.cfg, padded_generate=True)
        tok = self.tokenizer
        prompt_arr, lens, n_real = self._encode_rows(prompts, batch=None)
        n_new = self.rt.max_decode_steps if max_new_tokens is None else max_new_tokens
        gen_lib.check_sequence_budget(prompt_arr.shape[1], n_new, self.rt, self.cfg)
        limit = min(self.rt.max_seq_len, self.cfg.max_seq_len)
        if (
            self.rt.spec_decode
            and self.rt.temperature == 0.0
            and self.parallel is None
            and getattr(self, "draft_params", None) is not None
            and n_new >= 1
            # The verify pass overwrites up to k+1 slots past the budget;
            # near the sequence cap the plain loop still fits — fall through
            # there (transparent means never erroring where plain succeeds).
            and prompt_arr.shape[1] + self.rt.spec_k + 1 + n_new <= limit
        ):
            # Transparent routing: greedy speculative output is bit-identical
            # to the plain loop's, so callers (cluster workers, CLI) get the
            # speedup without an API change.
            return self._speculative_result(
                prompt_arr, lens, n_real, n_new, self.rt.spec_k
            )
        # Bucket only on the PLAIN path, after the budget check (which must
        # see the raw width, as before) and the spec-decode gate (whose
        # near-cap predicate on the raw width must keep routing prompts the
        # speculative loop can still fit).
        prompt_arr = self._bucket_prompt(prompt_arr, n_new)
        rng = jax.random.key(seed if seed is not None else self.rt.seed)

        profile_ctx = (
            profiling.trace(self.rt.profile_dir)
            if self.rt.profile_dir
            else contextlib.nullcontext()
        )
        t0 = time.perf_counter()
        with profile_ctx, profiling.span("engine.generate"):
            out = gen_lib.generate_tokens(
                self.params, self.cfg,
                jnp.asarray(prompt_arr), jnp.asarray(lens), rng,
                max_new_tokens=n_new,
                temperature=self.rt.temperature, top_k=self.rt.top_k, top_p=self.rt.top_p,
                eos_id=tok.eos_id, pad_id=tok.pad_id,
                forward_fn=self._forward_fn, make_cache=self._make_cache,
                decode_fn=self._decode_fn,
            )
            out = _to_host(out)[:n_real]
        dt = time.perf_counter() - t0
        profiling.record_memory_stats()

        texts = [tok.decode(row) for row in out]
        gen_count = int(out.shape[0] * out.shape[1])
        METRICS.inc("engine.generated_tokens", gen_count)
        return GenerationResult(
            text=texts, tokens=out,
            prompt_tokens=int(lens[:n_real].sum()), generated_tokens=gen_count,
            seconds=dt,
        )

    def _bucket_prompt(self, prompt_arr, n_new: int):
        """Pad the prompt width up the shared bucket ladder
        (runtime/shapes.py) so generate_tokens compiles once per bucket
        instead of once per distinct batch-max prompt length — the
        "recompile every new seq length" serving bug tools.graftcheck's GC4
        gate pins closed.  Exact by construction: pad slots carry pad_id,
        sit to the RIGHT of every real token (causal prefill queries never
        see them), and the decode mask admits only real prompt slots +
        generated slots.  Skipped when the bucket would not fit the
        sequence budget (keeps the pre-bucket error behavior) and under
        seq-parallelism (T must stay a multiple of the seq axis)."""
        if self.parallel is not None and self.parallel.seq_parallel:
            return prompt_arr
        t = int(prompt_arr.shape[1])
        limit = min(self.rt.max_seq_len, self.cfg.max_seq_len)
        target = shapes_lib.generate_pad_len(t, n_new, limit)
        if target <= t:
            return prompt_arr
        return jnp.pad(
            prompt_arr, ((0, 0), (0, target - t)),
            constant_values=self.tokenizer.pad_id,
        )

    # -- sessions: KV persists across turns; host spill under kv_host_spill --

    def _session_max_len(self) -> int:
        return min(self.rt.max_seq_len, self.cfg.max_seq_len)

    def _encode_rows(self, prompts: list[str], batch: int | None) -> tuple:
        """Encode + pad rows.  ``batch=None``: new session — pad the row count
        up to the mesh multiple.  Otherwise: continuation — row count must
        match the session's real rows; mesh-padding rows repeat row 0."""
        tok = self.tokenizer
        seqs = [tok.encode(p) for p in prompts]
        n_real = len(seqs)
        if batch is None:
            mult = self._batch_multiple()
            while len(seqs) % mult:
                seqs.append(seqs[0])
        else:
            while len(seqs) < batch:
                seqs.append(seqs[0])
        arr, lens = pad_batch(seqs, tok.pad_id)
        if self.parallel is not None and self.parallel.seq_parallel:
            # The seq-sharded prefill splits the prompt over the 'seq' axis;
            # right-pad T up to the mesh multiple (pad slots are masked out
            # of decode attention via prompt_lens, like any padding).
            seq_ax = self.parallel.mesh.shape["seq"]
            t = arr.shape[1]
            if t % seq_ax:
                pad = seq_ax - t % seq_ax
                arr = np.pad(arr, ((0, 0), (0, pad)), constant_values=tok.pad_id)
        return jnp.asarray(arr), jnp.asarray(lens), n_real

    def _session_turn(self, sess, chunk, lens, n_new: int, seed: int | None) -> GenerationResult:
        from . import session as session_lib

        t = int(chunk.shape[1])
        if sess.base + t + n_new > sess.max_len:
            raise ValueError(
                f"session {sess.sid}: {sess.base} used + {t} chunk + {n_new} "
                f"new tokens exceeds session max_len {sess.max_len}"
            )
        tok = self.tokenizer
        rng = jax.random.key(seed if seed is not None else self.rt.seed)
        t0 = time.perf_counter()
        with profiling.span("engine.generate"):
            toks, cache, valid, real, spos = session_lib.session_step(
                self.params, self.cfg, chunk, lens,
                sess.real_lens, sess.valid_mask, sess.cache,
                jnp.int32(sess.base), rng,
                max_new_tokens=n_new,
                temperature=self.rt.temperature, top_k=self.rt.top_k,
                top_p=self.rt.top_p, eos_id=tok.eos_id, pad_id=tok.pad_id,
                forward_fn=self._forward_fn,
                slot_positions=sess.slot_positions,
            )
            out = _to_host(toks)[: sess.n_real]
        dt = time.perf_counter() - t0
        sess.cache, sess.valid_mask, sess.real_lens = cache, valid, real
        sess.slot_positions = spos
        sess.base += t + n_new
        texts = [tok.decode(row) for row in out]
        gen_count = int(out.shape[0] * out.shape[1])
        METRICS.inc("engine.generated_tokens", gen_count)
        return GenerationResult(
            text=texts, tokens=out,
            prompt_tokens=int(np.asarray(lens)[: sess.n_real].sum()),
            generated_tokens=gen_count, seconds=dt,
        )

    def start_session(
        self, prompts: list[str], max_new_tokens: int | None = None,
        seed: int | None = None,
    ) -> tuple[str, GenerationResult]:
        """Open a session: prefill + decode, keeping the KV cache for
        continuation turns.  Returns (session_id, result)."""
        kv_cache.refuse_unpaged_state(self.cfg, sessions=True)
        n_new = self.rt.max_decode_steps if max_new_tokens is None else max_new_tokens
        max_len = self._session_max_len()
        chunk, lens, n_real = self._encode_rows(prompts, batch=None)
        b, t = int(chunk.shape[0]), int(chunk.shape[1])
        if t + n_new > max_len:  # validate BEFORE allocating/registering
            raise ValueError(
                f"prompt ({t} padded tokens) + {n_new} new tokens exceeds "
                f"session max_len {max_len}"
            )
        self.sessions.make_room()  # evict an LRU cache before allocating ours
        cache = self._make_cache(self.cfg, b, max_len)
        valid = jnp.zeros((b, max_len), dtype=bool)
        real = jnp.zeros((b,), jnp.int32)
        sess = self.sessions.new_session(cache, valid, real, base=0, max_len=max_len)
        sess.n_real = n_real
        if self.cfg.model_window is not None:
            # Sliding-window session state: the padded multi-turn layout
            # makes slot != position, and the window mask compares positions
            # (session_step maintains the map turn by turn).
            sess.slot_positions = jnp.zeros((b, max_len), jnp.int32)
        try:
            res = self._session_turn(sess, chunk, lens, n_new, seed)
        except Exception:
            self.sessions.drop(sess.sid)  # no orphaned HBM cache on failure
            raise
        return sess.sid, res

    def continue_session(
        self, sid: str, prompts: list[str], max_new_tokens: int | None = None,
        seed: int | None = None,
    ) -> GenerationResult:
        """Append a turn to an existing session (restoring its cache from
        host DRAM first if it was spilled)."""
        sess = self.sessions.get(sid)
        if len(prompts) != sess.n_real:
            raise ValueError(
                f"session {sid} has {sess.n_real} rows; got {len(prompts)} prompts"
            )
        self.sessions.ensure_resident(sess)
        self.sessions.touch(sess)
        n_new = self.rt.max_decode_steps if max_new_tokens is None else max_new_tokens
        batch = int(sess.valid_mask.shape[0])
        chunk, lens, _ = self._encode_rows(prompts, batch=batch)
        return self._session_turn(sess, chunk, lens, n_new, seed)

    def end_session(self, sid: str) -> None:
        self.sessions.drop(sid)

    # -- continuous batching ------------------------------------------------

    def continuous_batcher(
        self, batch_slots: int = 8, max_len: int | None = None,
        chunk_steps: int = 8, paged_pages: int | None = None,
        page_size: int | None = None,
        prefix_cache: bool | None = None,  # None -> rt.prefix_cache;
        #   automatic hash-block KV reuse over the paged pool (needs paged
        #   mode — a config-inherited flag degrades with a warning where
        #   paged itself does)
        speculative: bool | None = None,  # None -> rt.spec_decode; needs an
        #   attached draft + greedy + a single-device engine (contiguous
        #   OR paged — the target's KV rides the shared page pool and the
        #   draft/verify window writes through the page tables; prefix
        #   cache, int8 pages, the swap tier and mixed budgets all
        #   compose).  Mesh engines serve plain
        prefill_chunk: int | None = None,  # chunked prefill: admit at most
        #   this many prompt tokens per scheduling round PER PENDING
        #   prefill (contiguous or paged, single-device or dp/tp mesh —
        #   paged finishes allocate pool pages on demand at the splice;
        #   see ContinuousBatcher.  Not with a speculative draft)
        prefill_concurrency: int = 2,  # chunked prefills in flight at once
        #   (1 restores the old one-at-a-time head-of-line behavior)
        faults: Any = None,  # FaultPlane | None; None -> parse rt.faults —
        #   deterministic fault injection into the batcher's hot paths
        #   (runtime/faults.py), the lever behind `dlt-serve --fault`
        kv_bits: int | None = None,  # None -> rt.kv_bits; 8 = int8 KV
        #   pages in the paged pool (blockwise absmax scales, dequant
        #   fused into the decode read) — needs paged mode, like the
        #   prefix cache: explicit conflicts error, config-inherited ones
        #   degrade with a warning
        host_pages: int | None = None,  # None -> rt.host_pages; > 0 arms
        #   the host-RAM tier behind the pool (swap-preemption + prefix-
        #   cache spill) — same paged-mode degradation policy
        overlap: bool | None = None,  # None -> rt.overlap; dispatch-ahead
        #   engine loop: chunk N+1 dispatches from the device-resident
        #   carry while chunk N's host work overlaps on the CPU (temp-0
        #   bytes identical either way; mesh-legal — the carry is
        #   replicated scheduling state)
        schedule: str | None = None,  # None -> rt.schedule; "mixed"
        #   (default) fuses pending prefill-chunk bites into the decode
        #   step as one token-budget program (runtime/scheduler.py —
        #   decode rows never stall for a serialized prefill forward);
        #   "alternate" keeps the classic serialized rounds.  Temp-0
        #   bytes identical either way.
        token_budget: int | None = None,  # None -> rt.token_budget; the
        #   per-step token budget the mixed policy sizes prefill bites
        #   against (decode legs claim n_active of it first).  0/None =
        #   prefill_chunk-sized bites.
        tenant_weights: "str | dict | None" = None,  # None ->
        #   rt.tenant_weights; "gold:4,free:1"-style weights turn the
        #   mixed policy into per-tenant weighted-fair admission
        #   (runtime/scheduler.py TenantScheduler) — submit(tenant=)
        #   bills each request's virtual token counter.  "" disables.
        tenant_max_rows: int | None = None,  # None -> rt.tenant_max_rows;
        #   per-tenant resident-row cap (0 = uncapped).
    ):
        """A ContinuousBatcher over this engine's model: requests admit into
        an in-flight decode batch as rows free up (runtime/batcher.py) —
        no head-of-line blocking on mixed-length traffic.  Single-device
        engines and GSPMD data/tensor-parallel meshes — paged mode
        included (the pool shards KV heads over 'model'; per-chip
        capacity multiplies by the mesh); pipelined and sequence-parallel
        meshes keep their own decode schedules (the batcher constructor
        rejects them).  Paged mode is overload-safe:
        rows admit with prompt + one decode page, grow on demand at chunk
        boundaries, and a dry pool preempts the lowest-priority /
        most-recently-admitted row for recompute (temp-0 exact) instead of
        wedging — see submit(priority=, deadline=).
        """
        if self.parallel is not None and (
            self.parallel.pipelined or self.parallel.seq_parallel
        ):
            raise ValueError(
                "continuous batching requires a single-device engine or a "
                "pure data/tensor-parallel mesh (no pipe/seq axes)"
            )
        from .batcher import ContinuousBatcher

        # RuntimeConfig knobs are the defaults so the cluster worker's
        # mixed-budget endpoint serves paged when the config says to;
        # explicit arguments win (paged_pages=0 explicitly requests
        # contiguous even on a paged-configured engine).
        explicit = paged_pages is not None
        if paged_pages is None:
            paged_pages = self.rt.paged_pages
        if paged_pages == 0:
            paged_pages = None
        if page_size is None:
            page_size = self.rt.page_size
        explicit_cache = prefix_cache is not None
        if prefix_cache is None:
            prefix_cache = self.rt.prefix_cache
        if paged_pages is not None and self.parallel is not None:
            # Mesh-native paged serving: the pool shards its KV-head axis
            # over 'model' (batcher + models.kv_cache.pool_specs), so
            # the head count must divide.  Explicit requests that cannot
            # shard error loudly; a config-inherited paged_pages on a
            # mesh whose head count doesn't divide degrades to contiguous
            # with a warning (the shared cluster-config policy every
            # paged knob follows).
            tp = self.parallel.mesh.shape.get("model", 1)
            if tp > 1 and self.cfg.num_kv_heads % tp:
                if explicit:
                    raise ValueError(
                        f"paged KV on this mesh shards the pool on the "
                        f"KV-head axis: num_kv_heads "
                        f"{self.cfg.num_kv_heads} does not divide over "
                        f"'model' ({tp}); pass paged_pages=0 or reshape "
                        f"the mesh"
                    )
                log.warning(
                    "runtime.paged_pages=%d ignored: num_kv_heads %d does "
                    "not divide over the mesh 'model' axis (%d); serving "
                    "contiguous", paged_pages, self.cfg.num_kv_heads, tp,
                )
                paged_pages = None
        if prefix_cache and paged_pages is None:
            if explicit_cache:
                raise ValueError(
                    "automatic prefix caching needs the paged KV pool; "
                    "pass paged_pages (or set runtime.paged_pages)"
                )
            # Config-inherited flag on an engine that serves contiguous
            # (e.g. a mesh worker sharing a paged cluster config): degrade
            # instead of erroring, like paged itself does above.
            log.warning(
                "runtime.prefix_cache ignored: this engine serves "
                "contiguous KV (no paged pool to cache pages in)"
            )
            prefix_cache = False
        # KV memory tiering: int8 pages and the host-RAM tier both live
        # behind the paged pool — explicit requests on a non-paged engine
        # error; config-inherited ones degrade with a warning (the shared
        # cluster-config policy every paged knob above follows).
        explicit_bits = kv_bits is not None
        if kv_bits is None:
            kv_bits = self.rt.kv_bits
        if kv_bits not in (16, 8):
            raise ValueError(f"kv_bits must be 16 or 8, got {kv_bits}")
        if kv_bits == 8 and paged_pages is None:
            if explicit_bits:
                raise ValueError(
                    "int8 KV pages live in the paged pool; pass "
                    "paged_pages (or set runtime.paged_pages)"
                )
            log.warning(
                "runtime.kv_bits=8 ignored: this engine serves contiguous "
                "KV (full-width cache)"
            )
            kv_bits = 16
        explicit_host = host_pages is not None
        if host_pages is None:
            host_pages = self.rt.host_pages
        if host_pages and paged_pages is None:
            if explicit_host:
                raise ValueError(
                    "the host-RAM KV tier backs the paged pool; pass "
                    "paged_pages (or set runtime.paged_pages)"
                )
            log.warning(
                "runtime.host_pages ignored: this engine serves "
                "contiguous KV (no paged pool to tier)"
            )
            host_pages = 0
        if overlap is None:
            overlap = self.rt.overlap
        if schedule is None:
            schedule = self.rt.schedule
        if token_budget is None:
            token_budget = self.rt.token_budget
        if token_budget == 0:  # the CLI/config "disable" spelling
            token_budget = None
        if tenant_weights is None:
            tenant_weights = self.rt.tenant_weights
        if tenant_weights == "":  # the CLI/config "disable" spelling
            tenant_weights = None
        if tenant_max_rows is None:
            tenant_max_rows = self.rt.tenant_max_rows
        if tenant_max_rows == 0:
            tenant_max_rows = None
        if self.parallel is not None:
            # The shared cache shards its batch over 'data'; round the slot
            # count up so every mesh shape serves (extra slots are harmless
            # capacity — the constructor would otherwise reject e.g. the
            # default 8 on a data=16 mesh).
            dp = self.parallel.mesh.shape.get("data", 1)
            batch_slots = -(-batch_slots // dp) * dp
        explicit_spec = speculative is not None
        if speculative is None:
            # Config-driven default mirrors generate_text's routing: only
            # when every precondition holds (never erroring where the plain
            # batcher works).  temperature == 0 keeps the flip-on-spec
            # bit-exactness contract; sampled speculation (distribution-
            # preserving, different RNG stream) is available by passing
            # speculative=True explicitly.  Paged pools compose since
            # round 17 (the draft/verify window writes through the page
            # tables), so paged engines speculate by default too.
            speculative = (
                self.rt.spec_decode
                and self.rt.temperature == 0.0
                and self.parallel is None
                and getattr(self, "draft_params", None) is not None
            )
        if speculative and prefill_chunk is not None and not explicit_spec:
            # Config-inherited degrade (the shared cluster-config policy
            # every paged knob follows): a config with spec_decode on must
            # not brick a server that also chunks prefills — the draft
            # admission prefills monolithically, so speculation turns off
            # with a warning.  An explicit speculative=True still errors
            # loudly in the batcher constructor.
            log.warning(
                "runtime.spec_decode ignored: chunked prefill is "
                "configured (prefill_chunk=%d) and the speculative draft "
                "admission prefills monolithically; serving plain",
                prefill_chunk,
            )
            speculative = False
        if speculative and (tenant_weights or tenant_max_rows) \
                and not explicit_spec:
            # Same config-inherited degrade: tenant weighted-fair
            # scheduling and the speculative round ledger do not compose
            # yet (make_scheduler rejects the pair loudly when
            # speculative=True is explicit).
            log.warning(
                "runtime.spec_decode ignored: tenant weighted-fair "
                "scheduling is configured and does not compose with "
                "speculative rounds yet; serving plain",
            )
            speculative = False
        spec_kwargs = {}
        if speculative:
            if getattr(self, "draft_params", None) is None:
                raise ValueError(
                    "speculative batching needs a draft: call "
                    "attach_draft(...) first"
                )
            spec_kwargs = dict(
                draft_params=self.draft_params, draft_cfg=self.draft_cfg,
                spec_k=self.rt.spec_k,
                spec_adaptive_k=self.rt.spec_adaptive_k,
            )
        if faults is None and self.rt.faults:
            # Config-driven fault plane (operator drills / CI): each batcher
            # gets its OWN plane so once-only rules stay once-only per
            # serving lifetime, not per respawn (respawn() shares the
            # instance by reference, preserving already-fired counters).
            from .faults import FaultPlane

            faults = FaultPlane.parse(self.rt.faults)
        tok = self.tokenizer
        return ContinuousBatcher(
            self.cfg, self.params, tokenizer=tok,
            **spec_kwargs,
            batch_slots=batch_slots,
            max_len=min(max_len or self.rt.max_seq_len, self.cfg.max_seq_len),
            chunk_steps=chunk_steps,
            temperature=self.rt.temperature, top_k=self.rt.top_k,
            top_p=self.rt.top_p, eos_id=tok.eos_id, pad_id=tok.pad_id,
            kv_dtype=self.rt.kv_cache_dtype,
            parallel=self.parallel,
            paged_pages=paged_pages, page_size=page_size,
            prefix_cache=bool(prefix_cache),
            prefill_chunk=prefill_chunk,
            prefill_concurrency=prefill_concurrency,
            faults=faults,
            kv_bits=kv_bits, host_pages=int(host_pages),
            overlap=bool(overlap),
            schedule=schedule, token_budget=token_budget,
            tenant_weights=tenant_weights, tenant_max_rows=tenant_max_rows,
        )

    # -- speculative decoding (runtime/speculative.py): greedy-exact at
    # temperature 0, distribution-preserving sampling above it ----------

    def _serves_quantized(self) -> bool:
        """Whether the decoder-block weights are resident as QuantizedTensor
        leaves (serve_quantized stores) — such params cannot be re-quantized
        into a self-draft."""
        from ..checkpoint.quantize import QuantizedTensor

        leaves = jax.tree_util.tree_leaves(
            self.params.get("blocks", {}),
            is_leaf=lambda x: isinstance(x, QuantizedTensor),
        )
        return any(isinstance(x, QuantizedTensor) for x in leaves)

    def attach_draft(
        self, draft_cfg: Any = None, draft_params: Any = None,
        quantize_bits: int | None = None,
    ) -> None:
        """Attach a draft model for ``generate_text_speculative``.

        Either pass an explicit ``(draft_cfg, draft_params)`` pair (any
        model sharing this engine's vocabulary — a smaller family member is
        the classic choice), or ``quantize_bits=4|8`` for self-speculation:
        the draft is this engine's own decoder blocks weight-only quantized
        (reads a fraction of the weight bytes per draft step, agrees with
        the target often — and exactness never depends on how often).
        """
        if quantize_bits is not None:
            if draft_cfg is not None or draft_params is not None:
                raise ValueError("pass draft_cfg/draft_params OR quantize_bits")
            from ..checkpoint.quantize import quantize_tree

            if self._serves_quantized():
                raise ValueError(
                    "engine already serves quantized weights; build the "
                    "draft explicitly (attach_draft(draft_cfg, draft_params))"
                )
            draft_cfg = self.cfg
            draft_params = {
                **self.params,
                "blocks": quantize_tree(self.params["blocks"], bits=quantize_bits),
            }
        if draft_cfg is None or draft_params is None:
            raise ValueError("need draft_cfg + draft_params (or quantize_bits)")
        if draft_cfg.vocab_size != self.cfg.vocab_size:
            raise ValueError(
                f"draft vocab {draft_cfg.vocab_size} != target vocab "
                f"{self.cfg.vocab_size}"
            )
        self.draft_cfg = draft_cfg
        self.draft_params = draft_params

    def generate_text_speculative(
        self, prompts: list[str], max_new_tokens: int | None = None,
        k: int = 4, seed: int | None = None,
    ) -> GenerationResult:
        """Generation through the speculative decode loop — at temperature 0
        emits exactly ``generate_text``'s tokens; at temperature > 0 draws
        an exact sample from the same warped target distribution (rejection
        sampling — per-seed tokens differ from generate_text's because the
        RNG stream differs, the distribution does not).  Faster whenever the
        attached draft's acceptance covers its cost.  Single-device engines
        only (the loop drives models.model.forward directly)."""
        if getattr(self, "draft_params", None) is None:
            raise ValueError("no draft attached; call attach_draft(...) first")
        if self.parallel is not None:
            raise ValueError(
                "speculative decoding is single-device for now (mesh engines "
                "serve via generate_text / continuous_batcher)"
            )
        prompt_arr, lens, n_real = self._encode_rows(prompts, batch=None)
        n_new = self.rt.max_decode_steps if max_new_tokens is None else max_new_tokens
        gen_lib.check_sequence_budget(
            prompt_arr.shape[1] + k + 1, n_new, self.rt, self.cfg
        )
        return self._speculative_result(prompt_arr, lens, n_real, n_new, k, seed)

    def _speculative_result(
        self, prompt_arr, lens, n_real: int, n_new: int, k: int,
        seed: int | None = None,
    ) -> GenerationResult:
        """Shared tail of generate_text (spec_decode routing) and
        generate_text_speculative: inputs are pre-encoded and budget-checked.
        Mirrors the plain path's observability (profile trace,
        generate_seconds, memory stats) — flipping spec_decode on must not
        flatline a latency dashboard."""
        from .speculative import speculative_generate_tokens

        tok = self.tokenizer
        rng = (
            jax.random.key(seed if seed is not None else self.rt.seed)
            if self.rt.temperature > 0.0 else None
        )
        profile_ctx = (
            profiling.trace(self.rt.profile_dir)
            if self.rt.profile_dir
            else contextlib.nullcontext()
        )
        t0 = time.perf_counter()
        with profile_ctx, profiling.span("engine.generate"):
            out, stats = speculative_generate_tokens(
                self.params, self.cfg, self.draft_params, self.draft_cfg,
                jnp.asarray(prompt_arr), jnp.asarray(lens),
                k=k, max_new_tokens=n_new,
                eos_id=tok.eos_id, pad_id=tok.pad_id, return_stats=True,
                temperature=self.rt.temperature, top_k=self.rt.top_k,
                top_p=self.rt.top_p, rng=rng,
            )
            out = _to_host(out)[:n_real]
        dt = time.perf_counter() - t0
        profiling.record_memory_stats()
        drafted = max(int(stats["drafted"]), 1)
        METRICS.inc("engine.generated_tokens", int(out.shape[0] * out.shape[1]))
        METRICS.observe("engine.spec_acceptance",
                        int(stats["accepted"]) / drafted)
        return GenerationResult(
            text=[tok.decode(row) for row in out], tokens=out,
            prompt_tokens=int(np.asarray(lens)[:n_real].sum()),
            generated_tokens=int(out.shape[0] * out.shape[1]), seconds=dt,
        )
