"""User-facing serving API over the step-driven ``EngineCore``.

Three entry points, all driving the same core (and therefore the same
slots, page pools, and prefix cache):

* ``LLM.generate(prompts, params)`` — synchronous batch: submit every
  prompt, loop ``step()`` until all finish, return ``RequestOutput``s in
  submission order.
* ``LLM.stream(prompt, params)`` — incremental iterator: yields a
  ``StepOutput`` the moment the request emits tokens (the first chunk
  arrives at admission, long before completion). Other in-flight
  requests keep decoding on the shared core while a stream is consumed —
  their tokens accumulate on their Requests and are collected whenever
  their own ``generate``/``stream`` call drains.
* ``LLM.abort(uid)`` — cancel a queued or running request; its pages
  return to the pools refcount-exactly and any open stream for it ends.

``Session`` layers multi-turn chat on top: each ``send()`` submits
history + new user tokens as one prompt, so with the engine's prefix
cache on, turn N+1 aliases the pages turn N left behind and prefills
only the uncached suffix (retiring slots index their full sequence —
prompt AND generated tokens — into the radix tree when their dense pages
survive to retirement).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Iterator, List, Optional, Sequence, Union

import numpy as np

from repro.serving.engine import (EngineConfig, EngineCore, Request,
                                  StepOutput)
from repro.serving.sampling import SamplingParams


@dataclasses.dataclass
class RequestOutput:
    """Completed request: generated token ids + finish metadata."""
    uid: int
    prompt_token_ids: List[int]
    token_ids: List[int]
    finish_reason: str
    text: str = ""                 # detokenized (engines with a detokenizer)
    cached_tokens: int = 0         # prompt tokens served from the cache
    prefill_tokens: int = 0        # prompt tokens actually forwarded
    request: Optional[Request] = None   # timings (ttft/latency), slot, hits


def _is_single_prompt(prompts) -> bool:
    if isinstance(prompts, np.ndarray):
        return prompts.ndim == 1
    if isinstance(prompts, (list, tuple)) and prompts:
        return isinstance(prompts[0], (int, np.integer))
    return False


class LLM:
    """High-level frontend owning one ``EngineCore``.

    ``detokenizer``: optional ``List[int] -> str``; enables
    ``SamplingParams.stop`` strings and fills ``RequestOutput.text``.
    Extra keyword arguments build the ``EngineConfig`` when ``ecfg`` is
    not given (e.g. ``LLM(cfg, params, batch_slots=4, max_seq=128)``).
    """

    def __init__(self, cfg, params, ecfg: Optional[EngineConfig] = None, *,
                 detokenizer: Optional[Callable] = None, faults=None,
                 **ecfg_kw):
        if ecfg is None:
            ecfg = EngineConfig(**ecfg_kw)
        elif ecfg_kw:
            raise ValueError(f"pass ecfg OR EngineConfig kwargs, not both "
                             f"({sorted(ecfg_kw)})")
        self.core = EngineCore(cfg, params, ecfg, detokenizer=detokenizer,
                               faults=faults)
        self.detokenizer = detokenizer

    # -- driving -----------------------------------------------------------
    def _drive(self) -> List[StepOutput]:
        """One engine step; when nothing is admissible yet (open-loop
        arrivals), sleep until the next arrival so callers simply loop."""
        outs = self.core.step()
        if not outs and not self.core.has_active:
            t = self.core.next_arrival()
            if t is not None:
                time.sleep(max(1e-4, t - time.time()))
        return outs

    def _output_of(self, req: Request) -> RequestOutput:
        text = (self.detokenizer(list(req.generated))
                if self.detokenizer is not None else "")
        return RequestOutput(
            uid=req.uid, prompt_token_ids=list(map(int, req.prompt)),
            token_ids=list(req.generated), finish_reason=req.finish_reason,
            text=text, cached_tokens=req.cached_tokens,
            prefill_tokens=max(req.prefill_tokens, 0), request=req)

    # -- public API --------------------------------------------------------
    def generate(self, prompts,
                 params: Union[SamplingParams, Sequence[SamplingParams],
                               None] = None, *,
                 priority: int = 0) -> List[RequestOutput]:
        """Submit one prompt (flat token sequence) or a batch of prompts
        and block until all finish. ``params``: one ``SamplingParams``
        shared by every prompt, or one per prompt. ``priority``: the
        engine's preemption class (see ``EngineCore.add_request``)."""
        single = _is_single_prompt(prompts)
        batch = [prompts] if single else list(prompts)
        if params is None or isinstance(params, SamplingParams):
            plist = [params] * len(batch)
        else:
            plist = list(params)
            if len(plist) != len(batch):
                raise ValueError(f"{len(plist)} SamplingParams for "
                                 f"{len(batch)} prompts")
        reqs = [self.core.add_request(p, sp, priority=priority)
                for p, sp in zip(batch, plist)]
        while any(not r.finished for r in reqs):
            self._drive()
        outs = [self._output_of(r) for r in reqs]
        self.core.reap_done()   # keep the long-lived core's memory bounded
        return outs

    def stream(self, prompt, params: Optional[SamplingParams] = None, *,
               max_new_tokens: Optional[int] = None,
               priority: int = 0) -> Iterator[StepOutput]:
        """Submit one prompt and yield its tokens incrementally: one
        ``StepOutput`` per engine step that emitted tokens for THIS
        request (the admission chunk carries the first token; the final
        chunk always has ``finished=True`` — after an ``abort(uid)``
        between chunks it is an empty terminal chunk carrying
        ``finish_reason="aborted"``; every chunk carries the request's
        ``uid``). The request is submitted when iteration BEGINS (first
        ``__next__``), and abandoning the iterator (break / close / GC)
        aborts it — so a dropped stream, started or not, can never pin a
        batch slot, its pages, or a queue position.

        Chunks are cut against the Request's own token list, not this
        iterator's engine steps — tokens generated while ANOTHER
        frontend call (a concurrent ``generate``, or an interleaved
        second stream) drives the shared core are caught up on the next
        ``__next__``, never dropped."""
        def _gen():
            # Submitted HERE, not in stream(): an abandoned generator
            # that was never started has enqueued nothing (close()/GC on
            # an unstarted generator never runs the body, so an eager
            # add_request would orphan a queued request).
            req = self.core.add_request(prompt, params,
                                        max_new_tokens=max_new_tokens,
                                        priority=priority)
            emitted = 0
            delivered_fin = False
            try:
                while True:
                    new = [int(t) for t in req.generated[emitted:]]
                    fin = req.finished
                    if new:
                        emitted += len(new)
                        delivered_fin = fin
                        yield StepOutput(req.uid, new, fin,
                                         req.finish_reason)
                    if fin:
                        if not delivered_fin:   # out-of-band abort():
                            yield StepOutput(req.uid, [], True,
                                             req.finish_reason)
                        self.core.reap_done()   # bounded long-lived core
                        return
                    self._drive()
            finally:
                # abandoned mid-flight: release the slot and its pages
                if not req.finished:
                    self.core.abort(req.uid)

        return _gen()

    def abort(self, uid) -> bool:
        return self.core.abort(uid)


class Session:
    """Multi-turn chat session over one ``LLM``.

    Each ``send(user_tokens)`` submits ``history + user_tokens`` as the
    prompt and appends the reply to the history, so with
    ``EngineConfig.prefix_cache=True`` turn N+1 aliases the pages earlier
    turns already filled and prefills only the new user message
    (``RequestOutput.cached_tokens`` / ``prefill_tokens`` report the
    split; pages-saved shows up in the engine's allocator counters)."""

    def __init__(self, llm: LLM, params: Optional[SamplingParams] = None):
        self.llm = llm
        self.params = params
        self.history: List[int] = []       # prompt + reply tokens so far
        self.turns: List[RequestOutput] = []

    def send(self, user_tokens,
             params: Optional[SamplingParams] = None) -> RequestOutput:
        prompt = self.history + list(map(int, user_tokens))
        out = self.llm.generate(np.asarray(prompt, np.int32),
                                params if params is not None
                                else self.params)[0]
        self.history = prompt + list(out.token_ids)
        self.turns.append(out)
        return out
