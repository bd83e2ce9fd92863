"""Wire protocol for multi-host serving — key schema + message codecs.

Everything the router and the host workers exchange travels through a
:class:`~pytorch_distributed_tpu.distributed.store.Store` (TCPStore over
DCN in production, HashStore in-process for tests, FileStore over NFS).
The store gives us ordered-by-us primitives only (``set``/``get_nowait``/
``add``), so ordering and exactly-once are built here:

* **Channels, not host names.** Every worker registration claims a fresh
  *channel* index from the ``members`` counter; all of its keys live
  under ``{ns}/chan/{i}/``. A host that dies and rejoins registers again
  and gets a NEW channel, so a recovered worker can never replay the old
  channel's inbox or collide with its own stale outbox — the same
  join-counter pattern ``elastic.rendezvous.DynamicRendezvous`` uses for
  participant slots.

* **Single-writer logs.** The router appends to a channel's inbox
  (``in/{n}``, n from the ``in_seq`` counter, value written AFTER the
  counter bump so the reader never sees a gap); the worker appends to the
  outbox (``out/{n}``, n is worker-local — one writer needs no counter).
  Each side consumes its peer's log with a local cursor + ``get_nowait``,
  deleting entries behind the cursor so long-running deployments don't
  accrete keys.

* **Sequence numbers twice.** The outbox index orders the whole stream;
  each request's token chunks ALSO carry a per-request ``seq`` the router
  asserts on, so reassembly bugs fail loudly instead of corrupting a
  token stream.

* **Route incarnations.** Every routing attempt gets a fresh
  ``route_id``. Workers echo it on every chunk; the router drops chunks
  whose route_id is not the request's current one. That is the whole
  exactly-once story for failover: a host that was marked dead but is
  merely slow can keep decoding and publishing — its stream is simply
  ignored once the request has been re-admitted elsewhere.

Values are JSON — prompts and token chunks are small int lists, and JSON
keeps the protocol debuggable with nothing but ``store.get``.
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional

__all__ = ["Keys", "dumps", "loads", "DEFAULT_NAMESPACE"]

DEFAULT_NAMESPACE = "mhserve"


def dumps(obj: Dict[str, Any]) -> bytes:
    return json.dumps(obj, separators=(",", ":")).encode()


def loads(raw: bytes) -> Dict[str, Any]:
    return json.loads(raw.decode())


class Keys:
    """Key-name factory for one serving deployment (one namespace)."""

    def __init__(self, namespace: str = DEFAULT_NAMESPACE):
        self.ns = namespace

    # -- membership (join counter, rendezvous-style) -----------------------
    def members(self) -> str:
        return f"{self.ns}/members"

    def member(self, i: int) -> str:
        return f"{self.ns}/member/{i}"

    # -- per-channel request inbox (router -> worker) ----------------------
    def in_seq(self, chan: int) -> str:
        return f"{self.ns}/chan/{chan}/in_seq"

    def inbox(self, chan: int, n: int) -> str:
        return f"{self.ns}/chan/{chan}/in/{n}"

    # -- per-channel result outbox (worker -> router) ----------------------
    def outbox(self, chan: int, n: int) -> str:
        return f"{self.ns}/chan/{chan}/out/{n}"

    # -- load + heartbeat (one key: published together every worker loop) --
    def load(self, chan: int) -> str:
        return f"{self.ns}/chan/{chan}/load"

    # -- graceful-drain signal ---------------------------------------------
    def stop(self, chan: int) -> str:
        return f"{self.ns}/chan/{chan}/stop"

    # -- live weight push (reshard-while-serving checkpoint swap) ----------
    def weights(self, chan: int) -> str:
        return f"{self.ns}/chan/{chan}/weights"


# -- message constructors (shape documentation lives in one place) ---------

def announce_msg(host: str, chan: int, *, n_slots: int, prefill_len: int,
                 max_len: int, spec_k: int,
                 page_size: int = 0) -> Dict[str, Any]:
    """``page_size > 0`` marks a paged-cache host: its load snapshots carry
    a meaningful ``free_pages`` and the router sizes admissions in pages."""
    return {"host": host, "chan": chan, "n_slots": n_slots,
            "prefill_len": prefill_len, "max_len": max_len,
            "spec_k": spec_k, "page_size": page_size}


def wire_request(request_id: int, route_id: int, prompt: List[int],
                 max_new_tokens: int, eos_token: Optional[int],
                 arrival_unix: Optional[float] = None) -> Dict[str, Any]:
    """``arrival_unix`` is the wall clock (``time.time()``) at which the
    router took the request in: the one clock two hosts share, so the
    worker's queue wait includes the router's hop."""
    return {"request_id": request_id, "route_id": route_id,
            "prompt": prompt, "max_new_tokens": max_new_tokens,
            "eos_token": eos_token, "arrival_unix": arrival_unix}


def tokens_chunk(request_id: int, route_id: int, seq: int,
                 tokens: List[int]) -> Dict[str, Any]:
    return {"type": "tokens", "request_id": request_id,
            "route_id": route_id, "seq": seq, "tokens": tokens}


def finished_msg(request_id: int, route_id: int, seq: int, *, reason: str,
                 n_tokens: int, ttft_s: float, total_s: float,
                 queue_s: float = 0.0) -> Dict[str, Any]:
    return {"type": "finished", "request_id": request_id,
            "route_id": route_id, "seq": seq, "reason": reason,
            "n_tokens": n_tokens, "ttft_s": ttft_s, "total_s": total_s,
            "queue_s": queue_s}


def load_msg(*, hb: int, active: int, queued: int, n_slots: int,
             draining: bool, accept_num: int = 0,
             accept_den: int = 0, weights_version: int = 0,
             free_pages: int = -1) -> Dict[str, Any]:
    """``free_pages`` is the scheduler's admission capacity in KV pages
    (reservation-net for paged caches, free-slot page-equivalents for
    slotted ones); -1 means the worker predates the field."""
    return {"hb": hb, "active": active, "queued": queued,
            "n_slots": n_slots, "draining": draining,
            "accept_num": accept_num, "accept_den": accept_den,
            "weights_version": weights_version, "free_pages": free_pages}


def weights_msg(version: int, ckpt_dir: str,
                step: Optional[int]) -> Dict[str, Any]:
    """A live weight push: workers observing a version newer than the one
    they serve load ``ckpt_dir`` (at ``step``, None = latest) through their
    param_loader and swap it in between decode steps."""
    return {"version": version, "ckpt_dir": ckpt_dir, "step": step}
