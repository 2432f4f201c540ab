"""``ServeEngine``: the two compiled inference programs + direct-to-device
checkpoint loading.

Exactly TWO program shapes exist per served model (the compile-counter
gate in tests/test_serve.py):

- **prefill** — one program per prompt-length bucket, ``[1, bucket]``
  tokens at a dynamic cache offset (0 for a cold prompt; the hit length
  when a prefix-cache hit leaves only the cold tail to fill).  Prompts
  pad up to the smallest covering bucket (``utils.batching``); the
  padding rows write to the trash page and the returned logits are taken
  at the last REAL position.
- **decode** — ONE program at the fixed ``[max_batch]`` slot shape,
  advancing every active slot a single token per call.

With ``prefill_chunk=C`` (PR 17) the per-bucket prefill programs are
replaced wholesale by ONE fixed ``[1, C]`` chunk program — the same
prefill math, called repeatedly at successive cache offsets, so a single
executable covers every prompt length and the compiled-program set
shrinks from one-per-bucket to exactly two.

With a ``draft`` engine paired (ISSUE 18, speculative decoding) the
target's decode step is replaced by **verify** — ONE fixed ``[B, k+1]``
program scoring the pending token plus k draft proposals per slot while
writing the target KV pages, with the greedy accept/reject/rollback
math fused on (``models.decode.speculative_accept``).  The steady-state
hot loop is then exactly three compiled programs per pair: the draft
engine's decode step (run k times per tick at temperature 0), verify,
and the accept fused into verify.

Both donate the cache buffers (the pools are the big arrays; a decode
step must not double them) and both end in ``models.decode.sample_tokens``
so greedy/temperature sampling costs no third program.

``from_checkpoint`` is the PR 5 consumer path: it reads MANIFEST.json +
the per-process shard files and ``device_put``s each ``params`` leaf's
worker-0 row straight onto the serving mesh — leaf-streamed, so the full
training state (all N worker replicas + Adam moments) is never
materialized on the serving host.  The model architecture self-configures
from the manifest's ``metadata`` block (the ISSUE 7 checkpoint satellite)
instead of the user restating ``--model``/layer flags.
"""

from __future__ import annotations

import logging
import os
import re
import zlib
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import checkpoint as ckpt_lib
from ..models import decode as D
from ..models import get_model
from ..utils.batching import pad_to_bucket, pick_bucket
from .cache import PageAllocator, page_table_row, pages_needed

log = logging.getLogger(__name__)

_KEY_SEG = re.compile(r"\['([^']+)'\]")


# ----------------------------------------------------------------------
# Program builders (module-level so jit construction is single-shot per
# engine/bucket, cached in the engine — never rebuilt per call)
# ----------------------------------------------------------------------

def _build_decode_program(spec: D.DecodeSpec, seed: int):
    def step(params, kc, vc, tokens, lengths, page_table, temps, rids,
             active):
        num_valid = active.astype(jnp.int32)
        logits, kc, vc = D.forward_paged(
            spec, params, tokens[:, None], lengths, num_valid,
            page_table, kc, vc)
        logits = logits[:, 0]
        # the token being generated sits one past the token just written
        nxt = D.sample_tokens(logits, temps, rids, lengths + 1, seed)
        return nxt, logits, kc, vc

    return jax.jit(step, donate_argnums=(1, 2))


def _build_prefill_program(spec: D.DecodeSpec, seed: int):
    """Prefill ``num_valid`` tokens starting at cache position ``offset``.

    ``offset=0, num_valid=plen`` is the classic one-shot bucket prefill;
    a prefix-cache hit runs the same program over just the cold tail
    (``offset = hit tokens``), and the chunked path calls it at the fixed
    ``[1, C]`` shape once per chunk.  The sampled token is drawn at the
    absolute position ``offset + num_valid`` — for the final (or only)
    span of a prompt that is exactly the first generated position, so
    every path seeds sampling identically."""
    def prefill_step(params, kc, vc, tokens, num_valid, offset, page_row,
                     temp, rid):
        logits, kc, vc = D.forward_paged(
            spec, params, tokens, offset[None], num_valid[None],
            page_row[None], kc, vc)
        last = jnp.take_along_axis(
            logits[0], (num_valid - 1)[None, None], axis=0)[0]
        nxt = D.sample_tokens(last[None], temp[None], rid[None],
                              (offset + num_valid)[None], seed)
        return nxt[0], last, kc, vc

    return jax.jit(prefill_step, donate_argnums=(1, 2))


def _build_verify_program(spec: D.DecodeSpec, k: int):
    """Score one speculation burst in ONE fixed ``[B, k+1]`` program
    (ISSUE 18): the chunked-prefill machinery generalized to the decode
    batch — ``forward_paged`` at the current lengths returns per-position
    logits while writing the target KV pages for positions ``C .. C+k``,
    and the accept/reject/rollback math (``D.speculative_accept``) is
    FUSED onto the same program, so the burst costs one dispatch and
    only ``(emitted [B, k], acc [B])`` ever crosses to the host.
    Inactive rows ride along masked (``num_valid = 0`` routes their
    writes to the trash page), exactly like the decode step."""
    def verify_step(params, kc, vc, tokens, lengths, page_table, active):
        num_valid = jnp.where(active, k + 1, 0).astype(jnp.int32)
        logits, kc, vc = D.forward_paged(
            spec, params, tokens, lengths, num_valid, page_table, kc, vc)
        emitted, acc = D.speculative_accept(logits, tokens[:, 1:])
        return emitted, acc, kc, vc

    return jax.jit(verify_step, donate_argnums=(1, 2))


# ----------------------------------------------------------------------
# Direct-to-device checkpoint loading (worker-0 params row)
# ----------------------------------------------------------------------

def _parse_params_key(key: str) -> Optional[tuple[str, ...]]:
    """``.params['a']['b']`` -> ('a', 'b'); None for non-params leaves."""
    if not key.startswith(".params["):
        return None
    return tuple(_KEY_SEG.findall(key[len(".params"):]))


def _verified_shard_leaves(path: str, manifest: dict):
    """Iterate a sharded checkpoint's ``(key, piece_list)`` leaf entries,
    one shard FILE at a time, with the manifest's size + crc32 checks
    applied before any payload is decoded — the shared streaming core of
    ``load_params_row0`` and ``load_params_resident`` (at most one
    shard's payload is resident at a time; a missing file is skipped,
    corruption raises)."""
    from flax import serialization
    for fname, info in manifest["shards"].items():
        fp = os.path.join(path, fname)
        if not os.path.isfile(fp):
            continue
        with open(fp, "rb") as f:
            raw = f.read()
        if (len(raw) != int(info["bytes"])
                or zlib.crc32(raw) != int(info["crc32"])):
            raise ValueError(f"checkpoint shard {fp} is corrupt (size/crc "
                             "mismatch vs manifest)")
        payload = serialization.msgpack_restore(raw)
        del raw
        yield from payload["leaves"].items()
        del payload


def load_params_row0(path: str, sharding=None) -> dict:
    """Stream a sharded checkpoint's ``params`` leaves to device.

    Reads each shard file once, accumulates only the pieces covering the
    WORKER-0 row of each ``params`` leaf, and ``device_put``s a leaf the
    moment its row is complete — so neither the other worker replicas nor
    the optimizer/residual state ever land on the serving host, and at
    most one shard file plus the in-flight leaf rows are resident.
    Verifies crc32 per shard like ``checkpoint.host_tree``."""
    manifest = ckpt_lib._read_manifest(path)
    if not manifest:
        raise FileNotFoundError(f"no committed manifest under {path}")
    want: dict[tuple, dict] = {}
    for key, info in manifest["leaves"].items():
        segs = _parse_params_key(key)
        if segs is not None:
            want[segs] = info
    if not want:
        raise ValueError(f"checkpoint {path} has no params leaves")
    acc: dict[tuple, tuple[np.ndarray, int]] = {}
    device: dict[tuple, jax.Array] = {}
    for key, plist in _verified_shard_leaves(path, manifest):
        segs = _parse_params_key(key)
        if segs is None or segs not in want or segs in device:
            continue
        shape = tuple(want[segs]["shape"])
        for index, arr in plist:
            lo, hi = index[0]
            if not lo <= 0 < hi:
                continue   # piece does not cover the worker-0 row
            if segs not in acc:
                acc[segs] = (np.empty(shape[1:], arr.dtype), 0)
            buf, filled = acc[segs]
            buf[tuple(slice(a, b) for a, b in index[1:])] = arr[0]
            acc[segs] = (buf, filled + int(arr[0].size))
        if segs in acc and acc[segs][1] == int(
                np.prod(shape[1:], dtype=np.int64)):
            buf = acc.pop(segs)[0]
            device[segs] = (jax.device_put(buf, sharding)
                            if sharding is not None
                            else jax.device_put(buf))
    missing = [k for k in want if k not in device]
    if missing:
        raise ValueError(
            f"checkpoint {path} is missing worker-0 coverage for "
            f"{len(missing)} params leaves (first: {missing[0]}) — "
            "multi-host checkpoints need a shared filesystem")
    out: dict = {}
    for segs, arr in device.items():
        node = out
        for s in segs[:-1]:
            node = node.setdefault(s, {})
        node[segs[-1]] = arr
    return out


def load_params_resident(path: str, meta: dict, sharding=None) -> dict:
    """Stream a SCATTER-RESIDENT sharded checkpoint's params to device
    (ISSUE 12 satellite: PR 11 left a hard refusal here).

    A resident checkpoint stores the consensus params as 1/N bucket
    shard rows (``.params_resident['bNNNN']`` leaves, ``[N, padded/N]``
    each) instead of ``.params`` leaves — there is no worker-0 row to
    stream.  But the manifest METADATA records the per-worker leaf
    template (``params_leaves``) and the bucket size, so the consensus
    unpacks template-free: accumulate each bucket's full row matrix
    across shard files (crc-verified), concatenate the rows
    (``comms.resident_to_tree`` — the host twin of the round-entry
    gather, bit-exact), and ``device_put`` per leaf.  The worker rows
    are 1/N each, so peak host residency stays one bucket matrix + the
    in-flight leaves."""
    tmpl_rows = meta.get("params_leaves")
    if not tmpl_rows:
        raise ValueError(
            f"checkpoint {path} stores scatter-resident params but its "
            "metadata carries no params_leaves template (saved by a "
            "pre-ISSUE-12 engine) — restore+re-save with the current "
            "engine, or with --param_residency replicated")
    template: dict = {}
    for segs, shape, dtype in tmpl_rows:
        node = template
        for s in segs[:-1]:
            node = node.setdefault(s, {})
        node[segs[-1]] = jax.ShapeDtypeStruct(tuple(shape),
                                              np.dtype(dtype))
    manifest = ckpt_lib._read_manifest(path)
    if not manifest:
        raise FileNotFoundError(f"no committed manifest under {path}")
    want = {key: info for key, info in manifest["leaves"].items()
            if key.startswith(".params_resident[")}
    if not want:
        raise ValueError(
            f"checkpoint {path} claims resident params but has no "
            ".params_resident leaves")
    acc: dict[str, tuple[np.ndarray, int]] = {}
    for key, plist in _verified_shard_leaves(path, manifest):
        if key not in want:
            continue
        shape = tuple(want[key]["shape"])
        for index, arr in plist:
            if key not in acc:
                acc[key] = (np.empty(shape, arr.dtype), 0)
            buf, filled = acc[key]
            buf[tuple(slice(a, b) for a, b in index)] = arr
            acc[key] = (buf, filled + int(arr.size))
    resident: dict = {}
    for key, (buf, filled) in acc.items():
        if filled != int(np.prod(buf.shape, dtype=np.int64)):
            raise ValueError(
                f"checkpoint {path}: resident bucket {key} is missing "
                "shard coverage — multi-host checkpoints need a shared "
                "filesystem")
        resident[_KEY_SEG.findall(key[len(".params_resident"):])[0]] = buf
    missing = [k for k in want if k not in acc]
    if missing:
        raise ValueError(
            f"checkpoint {path} is missing resident buckets "
            f"{missing[:3]}...")
    from .. import comms
    bucket_bytes = max(1, int(float(meta.get("sync_bucket_mb", 4.0))
                              * (1 << 20)))
    n_slices = int(meta.get("num_slices", 1) or 1)
    if n_slices > 1:
        # hierarchical checkpoint (ISSUE 13): rows stack S slices of W
        # inner shards and each SLICE has its own consensus — serve
        # takes slice 0's (rows 0..W-1), the same rank-0 convention the
        # training engine's final eval uses
        rows = int(next(iter(resident.values())).shape[0])
        if rows % n_slices:
            raise ValueError(
                f"checkpoint {path}: resident rows ({rows}) not "
                f"divisible by the manifest's num_slices ({n_slices})")
        w = rows // n_slices
        resident = {k: v[:w] for k, v in resident.items()}
    tree = comms.resident_to_tree(resident, template,
                                  bucket_bytes=bucket_bytes)
    return jax.tree_util.tree_map(
        lambda x: (jax.device_put(x, sharding) if sharding is not None
                   else jax.device_put(x)), tree)


def manifest_num_classes(path: str) -> Optional[int]:
    """Vocabulary size recovered from a sharded checkpoint's manifest
    leaf shapes (``.params['tok_emb']['embedding']`` is
    ``[workers, vocab, hidden]`` for every autoregressive family) — the
    fallback that lets metadata-less (pre-metadata) checkpoints serve
    with an explicit ``--model``."""
    manifest = ckpt_lib._read_manifest(path)
    info = (manifest or {}).get("leaves", {}).get(
        ".params['tok_emb']['embedding']")
    if not info or len(info.get("shape", ())) != 3:
        return None
    return int(info["shape"][1])


def model_from_metadata(meta: dict):
    """Rebuild the serving model from a checkpoint's manifest metadata."""
    name = meta.get("model", "")
    arch = meta.get("arch") or {}
    missing = [what for what, has in (
        ("latent attention", arch.get("latent")),
        ("routed experts", arch.get("experts")),
        ("sliding-window attention", "sliding" in arch.get("layer_types", ())))
        if has]
    if missing:
        raise ValueError(
            f"checkpoint was trained with --model {name!r}, whose "
            f"{', '.join(missing)} the paged decode path (models/decode.py) "
            "does not compute: it is not served yet")
    if not name.startswith(("gpt", "llama")):
        raise ValueError(
            f"checkpoint was trained with --model {name!r}; serving "
            "supports the autoregressive families (gpt_*/llama_*)")
    if not meta.get("scan_layers", False):
        raise ValueError(
            "checkpoint was saved with an unrolled (non-layer-scan) "
            "parameter layout; serving decodes over the stacked stack — "
            "retrain/save with --layer_scan auto|on")
    dtype = (jnp.bfloat16 if meta.get("compute_dtype") == "bfloat16"
             else jnp.float32)
    kw: dict[str, Any] = dict(num_classes=int(meta["num_classes"]),
                              dtype=dtype, scan_layers=True)
    if meta.get("num_kv_heads"):
        kw["num_kv_heads"] = int(meta["num_kv_heads"])
    if meta.get("num_experts"):
        kw["num_experts"] = int(meta["num_experts"])
        kw["capacity_factor"] = float(meta.get("capacity_factor", 1.25))
    return get_model(name, **kw)


# ----------------------------------------------------------------------
# The engine
# ----------------------------------------------------------------------

class ServeEngine:
    """Paged-KV inference engine for one (model, params) pair.

    Holds the page pools + the two compiled programs; the continuous-
    batching policy lives in ``serve.scheduler``.  ``max_seq`` bounds the
    positions any sequence may reach (page-table width =
    ``ceil(max_seq / page_size)``); defaults to twice the largest prompt
    bucket."""

    def __init__(self, model, params, *, max_batch: int = 4,
                 page_size: int = 16, max_pages: int = 64,
                 prompt_buckets=(16, 64), max_seq: Optional[int] = None,
                 mesh=None, seed: int = 0, prefix_cache: bool = False,
                 prefill_chunk: int = 0,
                 draft: Optional["ServeEngine"] = None,
                 spec_tokens: int = 0):
        self.spec = D.spec_from_model(model)
        self.model = model
        if page_size < 1 or max_batch < 1:
            raise ValueError(
                f"page_size ({page_size}) and max_batch ({max_batch}) "
                "must be >= 1")
        buckets = tuple(sorted(set(int(b) for b in prompt_buckets)))
        if not buckets or buckets[0] < 1:
            raise ValueError(
                f"prompt_buckets must be positive lengths, got "
                f"{prompt_buckets}")
        self.prompt_buckets = buckets
        self.max_batch = int(max_batch)
        self.page_size = int(page_size)
        self.max_seq = int(max_seq) if max_seq else 2 * buckets[-1]
        if self.max_seq < buckets[-1]:
            raise ValueError(
                f"max_seq {self.max_seq} below the largest prompt bucket "
                f"{buckets[-1]}")
        if self.spec.max_len and self.max_seq > self.spec.max_len:
            raise ValueError(
                f"max_seq {self.max_seq} exceeds the model's position "
                f"table ({self.spec.max_len})")
        self.pages_per_seq = pages_needed(self.max_seq, self.page_size)
        self.prefill_chunk = int(prefill_chunk)
        if self.prefill_chunk < 0 or (self.prefill_chunk
                                      and self.prefill_chunk
                                      % self.page_size):
            raise ValueError(
                f"prefill_chunk must be a positive multiple of page_size "
                f"({self.page_size}) so chunk boundaries land on page "
                f"boundaries, got {self.prefill_chunk}")
        self.prefix_cache = bool(prefix_cache)
        if self.prefix_cache and self.pages_per_seq >= max_pages - 1:
            raise ValueError(
                f"prefix_cache needs page-pool headroom beyond one "
                f"max-length sequence: a {self.max_seq}-token sequence "
                f"pins {self.pages_per_seq} of the {max_pages - 1} usable "
                f"pages (page 0 is the trash page), so nothing could ever "
                f"stay cached — raise max_pages")
        self.allocator = PageAllocator(max_pages)
        self.seed = int(seed)
        self._sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            self._sharding = NamedSharding(mesh, P())
        def _stage(x):
            return (jax.device_put(x, self._sharding)
                    if self._sharding is not None else jnp.asarray(x))

        self.params = jax.tree_util.tree_map(_stage, params)
        kc, vc = D.init_paged_cache(self.spec, max_pages, self.page_size)
        self.kcache, self.vcache = _stage(kc), _stage(vc)
        # compiled-memory observability (ISSUE 15): both serve programs
        # ride probe.TrackedProgram (AOT compile on first call, the
        # executable handle retained so memory_report reads
        # memory_analysis() without re-lowering).  The decode step has
        # ONE fixed shape (single-shape mode: zero per-call bookkeeping
        # on the hot loop); the prefill program specializes per prompt
        # bucket (multi_shape: one executable per bucket, keyed on the
        # admission path — not hot)
        from ..probe import TrackedProgram
        self._decode = TrackedProgram(
            "decode_step", _build_decode_program(self.spec, self.seed))
        self._prefill = TrackedProgram(
            "prefill", _build_prefill_program(self.spec, self.seed),
            multi_shape=True)
        # the chunk program is the SAME prefill math pinned to one
        # [1, prefill_chunk] shape — its own jit instance in single-shape
        # mode, so the per-chunk hot path pays zero shape bookkeeping
        self._chunk = (TrackedProgram(
            "prefill_chunk", _build_prefill_program(self.spec, self.seed))
            if self.prefill_chunk else None)
        self.compiled_buckets: list[int] = []
        # speculative decoding (ISSUE 18): pair a DRAFT engine onto this
        # (target) one.  Every pairing constraint is checked eagerly —
        # a bad pair fails at construction with the real reason, never
        # three ticks into a serve run
        self.draft = draft
        self.spec_tokens = int(spec_tokens)
        self._verify = None
        if (draft is None) != (self.spec_tokens == 0):
            raise ValueError(
                "speculative decoding needs BOTH a draft engine and "
                "spec_tokens >= 1 (--serve_draft_ckpt + "
                "--serve_spec_tokens): the draft proposes, spec_tokens "
                "sizes the verify program — one without the other is "
                "inert")
        if draft is not None:
            if self.spec_tokens < 1:
                raise ValueError(
                    f"spec_tokens must be >= 1, got {self.spec_tokens}")
            if draft.spec.vocab != self.spec.vocab:
                raise ValueError(
                    f"draft/target vocabulary mismatch ({draft.spec.vocab}"
                    f" vs {self.spec.vocab}): the draft proposes TOKEN "
                    "IDS that the target's verify logits score — the two "
                    "models must share one id space, or acceptance would "
                    "compare ids from different vocabularies")
            if draft.spec.num_experts:
                raise ValueError(
                    "MoE draft model rejected: the serving MoE decode "
                    "computes EVERY expert's FFN densely and combines by "
                    "the top-1 gate (models/decode._moe_ffn), so an MoE "
                    "draft costs strictly more per step than its dense "
                    "twin of the same hidden size — a draft exists to be "
                    "cheap; use a dense draft checkpoint")
            if draft.draft is not None:
                raise ValueError("draft engines cannot nest: the draft "
                                 "of a pair must be a plain engine")
            mismatch = [
                (n, getattr(draft, n), getattr(self, n))
                for n in ("max_batch", "page_size", "max_seq",
                          "prompt_buckets", "prefill_chunk",
                          "prefix_cache")
                if getattr(draft, n) != getattr(self, n)]
            mismatch += [("max_pages", draft.allocator.max_pages,
                          self.allocator.max_pages)
                         ] if (draft.allocator.max_pages
                               != self.allocator.max_pages) else []
            if mismatch:
                raise ValueError(
                    "draft/target engine geometry must match so the two "
                    "page pools stay position-for-position paired (one "
                    "page table schedule, joint admission): mismatched "
                    + ", ".join(f"{n} ({a} vs {b})"
                                for n, a, b in mismatch))
            self._verify = TrackedProgram(
                "verify",
                _build_verify_program(self.spec, self.spec_tokens))

    def memory_programs(self) -> dict:
        """Label -> TrackedProgram registry (the serve twin of
        ``LocalSGDEngine.memory_programs``): the fixed-batch decode step
        plus one prefill executable per compiled prompt bucket — or, when
        chunked prefill is on, the single fixed-shape chunk program."""
        out = {"decode_step": self._decode, "prefill": self._prefill}
        if self._chunk is not None:
            out["prefill_chunk"] = self._chunk
            if not self.compiled_buckets:
                # chunking replaced bucket prefill entirely this run —
                # an uncompiled bucket program is absence, so don't let
                # it flip ``available`` off
                del out["prefill"]
        if self.draft is not None:
            # speculative pair: the target's hot program is the fused
            # verify; its plain decode step never dispatches (absence,
            # like the bucket-prefill case above).  The draft's programs
            # report under a draft_ prefix so one memory table covers
            # the whole pair
            out["verify"] = self._verify
            del out["decode_step"]
            out.update({f"draft_{k}": v
                        for k, v in self.draft.memory_programs().items()})
        return out

    # -- construction from a sharded checkpoint ------------------------
    @classmethod
    def from_checkpoint(cls, ckpt_dir: str, *, mesh=None, model=None,
                        **engine_kw) -> "ServeEngine":
        """Build the engine off a PR 5 sharded checkpoint directory (the
        checkpoint root or one committed ``ckpt_<E>`` epoch dir): model
        architecture from the manifest metadata, params streamed leaf-by-
        leaf onto the serving mesh (worker-0 row only, no host
        full-gather).  Pass ``model=`` only for metadata-less legacy
        checkpoints."""
        path = ckpt_dir
        if not os.path.isfile(os.path.join(path, ckpt_lib.MANIFEST)):
            path = ckpt_lib.latest_checkpoint(ckpt_dir)
            if path is None:
                raise FileNotFoundError(
                    f"no committed checkpoint under {ckpt_dir}")
            if not os.path.isdir(path):
                raise ValueError(
                    f"{path} is a legacy single-file checkpoint; serving "
                    "loads the sharded (format 2) layout — re-save with "
                    "the CheckpointEngine")
        meta = ckpt_lib.manifest_metadata(path)
        resident = meta.get("param_residency") == "resident"
        if model is None:
            if not meta:
                raise ValueError(
                    f"checkpoint {path} carries no serve metadata (saved "
                    "by a pre-metadata engine?) — pass model= explicitly")
            model = model_from_metadata(meta)
        sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            sharding = NamedSharding(mesh, P())
        if resident:
            # ISSUE 12 satellite: a scatter-resident checkpoint stores
            # the consensus as 1/N bucket shard rows — unpack them
            # against the manifest-metadata leaf template (bit-exact,
            # the host twin of the round-entry gather) instead of the
            # PR 11 refusal
            params = load_params_resident(path, meta, sharding)
        else:
            params = load_params_row0(path, sharding)
        log.info("serve: restored %s params from %s (%s layout) onto %s",
                 meta.get("model") if meta else type(model).__name__,
                 path, "resident" if resident else "replicated",
                 "mesh" if mesh is not None else "default device")
        return cls(model, params, mesh=mesh, **engine_kw)

    # -- page math -----------------------------------------------------
    def pages_for(self, total_tokens: int) -> int:
        return pages_needed(total_tokens, self.page_size)

    def page_bytes(self) -> int:
        """Bytes one page pins across BOTH pools and every layer — the
        unit of the byte-exact occupancy accounting."""
        itemsize = np.dtype(self.spec.dtype).itemsize
        return (2 * self.spec.num_layers * self.page_size
                * self.spec.num_kv_heads * self.spec.head_dim * itemsize)

    def table_row(self, pages: list[int]) -> np.ndarray:
        return page_table_row(pages, self.pages_per_seq)

    # -- the two programs ----------------------------------------------
    def prefill(self, prompt, page_row: np.ndarray, temperature: float,
                rid: int, *, offset: int = 0) -> tuple[int, jax.Array]:
        """Run one prompt through the prefill program at its bucket
        shape, filling the sequence's pages; returns (first sampled
        token, last-position logits).  ``offset`` is the cache position
        the span starts at — 0 for a cold prompt, the hit length when a
        prefix-cache hit leaves only the cold tail (the bucket then
        covers just the tail).  The logits stay a DEVICE array — the hot
        admission path only needs the sampled token, so the [vocab]
        fetch is paid only by callers that read them."""
        prompt = np.asarray(prompt, np.int32)
        plen = int(prompt.shape[0])
        bucket = pick_bucket(plen, self.prompt_buckets)
        if bucket not in self.compiled_buckets:
            self.compiled_buckets.append(bucket)
        padded = pad_to_bucket(prompt, bucket)[None]
        nxt, last, self.kcache, self.vcache = self._prefill(
            self.params, self.kcache, self.vcache, jnp.asarray(padded),
            jnp.asarray(plen, jnp.int32), jnp.asarray(offset, jnp.int32),
            jnp.asarray(page_row),
            jnp.asarray(temperature, jnp.float32),
            jnp.asarray(rid, jnp.int32))
        return int(nxt), last

    def prefill_chunk_step(self, chunk, offset: int,
                           page_row: np.ndarray, temperature: float,
                           rid: int) -> tuple[int, jax.Array]:
        """Advance one prompt by ONE ``[1, prefill_chunk]`` chunk at
        cache position ``offset``; returns (sampled token, logits at the
        chunk's last valid position).  Intermediate chunks' samples are
        discarded by the scheduler; the FINAL chunk's sample is drawn at
        ``offset + num_valid == prompt_len`` — bit-for-bit the position
        the monolithic prefill samples at."""
        if self._chunk is None:
            raise RuntimeError("engine built without prefill_chunk")
        chunk = np.asarray(chunk, np.int32)
        nvalid = int(chunk.shape[0])
        if not 0 < nvalid <= self.prefill_chunk:
            raise ValueError(
                f"chunk of {nvalid} tokens outside (0, "
                f"{self.prefill_chunk}]")
        padded = pad_to_bucket(chunk, self.prefill_chunk)[None]
        nxt, last, self.kcache, self.vcache = self._chunk(
            self.params, self.kcache, self.vcache, jnp.asarray(padded),
            jnp.asarray(nvalid, jnp.int32), jnp.asarray(offset, jnp.int32),
            jnp.asarray(page_row),
            jnp.asarray(temperature, jnp.float32),
            jnp.asarray(rid, jnp.int32))
        return int(nxt), last

    def decode(self, tokens, lengths, page_table, temps, rids, active
               ) -> tuple[np.ndarray, jax.Array]:
        """One batched decode step at the fixed max_batch shape; rows
        with ``active == 0`` write to the trash page and their outputs
        are meaningless.  Returns (next tokens [B] on host, logits
        [B, vocab] as a DEVICE array — the decode loop discards them, so
        only readers pay the [B, vocab] device-to-host copy)."""
        nxt, logits, self.kcache, self.vcache = self._decode(
            self.params, self.kcache, self.vcache,
            jnp.asarray(tokens, jnp.int32) if not isinstance(
                tokens, jax.Array) else tokens,
            jnp.asarray(lengths, jnp.int32), jnp.asarray(page_table),
            jnp.asarray(temps, jnp.float32), jnp.asarray(rids, jnp.int32),
            jnp.asarray(active, jnp.bool_))
        return np.asarray(nxt), logits

    def verify(self, tokens, lengths, page_table, active
               ) -> tuple[np.ndarray, np.ndarray]:
        """Score one speculation burst: ``tokens [B, k+1]`` (pending
        token + k draft proposals per row) at cache offsets ``lengths``;
        writes the target KV for positions ``C .. C+k`` and returns the
        fused accept verdict ``(emitted [B, k], acc [B])`` on host —
        row i commits ``emitted[i, :acc[i] + 1]``.  Greedy-only by
        construction (the eager config rejection keeps temperature x
        speculation out)."""
        if self._verify is None:
            raise RuntimeError("engine built without a draft pair")
        emitted, acc, self.kcache, self.vcache = self._verify(
            self.params, self.kcache, self.vcache,
            jnp.asarray(tokens, jnp.int32),
            jnp.asarray(lengths, jnp.int32), jnp.asarray(page_table),
            jnp.asarray(active, jnp.bool_))
        return np.asarray(emitted), np.asarray(acc)
