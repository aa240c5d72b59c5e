"""Continuous-batching serving engine.

Production serving never decodes lock-step batches: requests arrive and
finish at different times, so the engine keeps a fixed pool of KV-cache
*slots* and every decode launch advances whichever slots are live, each at
its own position (`decode_step` accepts an (B,) position vector). A finished
request's slot is handed to the next queued request immediately — no
drain-the-batch bubbles.

Configuration-wall connection: the per-launch descriptor is a few dozen
bytes against a device-resident multi-GiB cache, and the weights, the cache
and (under fused sampling) the input tokens stay on the device. Each launch
still copies every leaf it passes to the jitted step from host to device —
the changing fields and the unchanged masks alike (``h2d``, below). Two
designs narrow that boundary:

* **Fused sampling** (``sampling="fused"``, the default): the decode launch
  runs the greedy-sampling epilogue on-device
  (:meth:`~repro.models.model.Model.decode_and_sample`, backed by the
  ``kernels/sampling.py`` Pallas kernel) and returns ``(B, 1)`` token ids —
  the host blocks on a few bytes instead of the full ``(B, vocab)`` logits.
  Because the sampled ids stay device-resident and feed the next launch
  directly, the decode descriptor drops its ``tokens`` leaf entirely: the
  host injects tokens only through ``token_overrides``/``override_mask``
  (admissions and freed slots). The decode launch copies those two,
  ``positions`` and ``live_mask``. ``sampling="host"`` keeps the classic
  logits-returning launch (the A/B baseline, bit-identical token streams).

* **Batched prefill**: admission runs the prompt through
  :meth:`~repro.models.model.Model.prefill_chunk` — ``ceil(p/chunk)``
  launches instead of p full-batch steps, each advancing *only* the
  admitted slot (other slots' cache rows stay bit-identical through an
  admission). On the dense trunk a launch is one causal forward of the
  chunk's tokens that writes only the slot's new K/V rows; the other
  families scan one masked decode step per token
  (:attr:`~repro.models.model.Model.prefill_path`). The prefill descriptor
  (``prefill_tokens``/``prefill_len``/``slot_mask``) is priced by the
  bridge like any other launch.

* **A decode launch staged ahead** (fused sampling): where the next decode
  launch's slots and positions are fixed without the ids of the one in
  flight, ``step()`` dispatches it before reading those ids, and reads them
  while it runs. This is the paper's *concurrent configuration*: the host's
  turn (descriptor, copies, dispatch, retirement) hides behind the device's
  step instead of following it. It holds when every slot is live, no
  request waits, and no request receives its last token by length in the
  launch in flight; positions advance at launch, one per decode launch for
  each live slot. Only an EOS is learned one launch late: the row decoded
  past it goes to no request (``ahead.dropped_rows``). Staging does not
  engage with a free slot or a waiting request (an arrival would wait one
  more decode step), after a launch that ends a request by length, or
  under host sampling, whose ids cross through the host: those steps
  launch, read and retire in turn, as the *sequential configuration* does.

Every launch goes through a :class:`~repro.dispatch.ScheduledExecutor`
(``engine.executor``), whose depth-bounded staging ring keeps prefill
launches in flight while the host prepares the next one — the serving twin
of OpenGeMM's staged configuration. The executor's
:class:`~repro.sched.state_cache.ConfigStateCache` (aliased as
``engine.config_cache``) counts which descriptor fields changed since the
last launch; ``engine.config_traffic()`` reports that split, which is an
account and not the copy. The copies launches really make are counted in
``engine.h2d``, by launch kind.

Tracing: ``step()`` and the layers under it open ``jax.profiler`` spans
(``serving.*`` here, ``dispatch.*`` in the executor) on the thread that runs
the loop, so a profiler trace puts the host's work beside the device's on
one clock. Their arguments are computed only while a profiler is tracing.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.dispatch import ScheduledExecutor


@dataclass
class Request:
    uid: int
    prompt: list[int]
    max_new_tokens: int
    generated: list[int] = field(default_factory=list)
    done: bool = False
    submitted_at: float = 0.0  # time.perf_counter() at submit()


@dataclass
class AheadCount:
    """Decode launches staged ahead of the read of the launch before them,
    and the rows those launches decoded for a request that had by then
    ended on an EOS (read, and given to no request)."""

    launches: int = 0
    dropped_rows: int = 0


@dataclass
class _DecodeLaunch:
    """A decode launch whose ids are not read yet: its output, and for
    each slot it decoded, the request it decoded for and whether it holds
    that request's last token by length."""

    out: object
    rows: list[tuple[int, Request, bool]]


@dataclass
class H2DCount:
    """Host→device copies that launches of one kind made: the numpy leaves
    ``_device_fn`` passes through ``jnp.asarray``."""

    launches: int = 0
    copies: int = 0
    bytes: int = 0


class ServingEngine:
    def __init__(self, model, params, *, max_slots: int = 4, max_len: int = 256,
                 eos_id: int | None = None, launch_depth: int = 2,
                 decode_fn=None, prefill_fn=None, on_launch=None,
                 sampling: str = "fused", sample_backend: str = "xla",
                 prefill_chunk: int = 8):
        assert sampling in ("fused", "host"), sampling
        assert prefill_chunk >= 1, prefill_chunk
        self.model = model
        self.params = params
        self.max_slots = max_slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.sampling = sampling
        self.prefill_chunk = prefill_chunk
        self.cache = model.init_cache(max_slots, max_len)
        self.positions = np.zeros((max_slots,), np.int32)
        # host mirror of each slot's pending input token (the descriptor
        # field in host mode; bookkeeping only under fused sampling, where
        # the device-resident ids are the real input ring)
        self.tokens = np.zeros((max_slots, 1), np.int32)
        # fused sampling: host→device token injections for the next decode
        # launch (admitted prompts' last token; zero for freed slots) —
        # all-False mask in steady state; both leaves are copied every
        # decode launch all the same
        self._overrides = np.zeros((max_slots,), np.int32)
        self._override_mask = np.zeros((max_slots,), bool)
        if sampling == "fused":
            # the device-resident sampled ids (previous launch's output,
            # next launch's input — never crosses the boundary)
            self._dev_tokens = jnp.zeros((max_slots, 1), jnp.int32)
        self.slot_req: list[Request | None] = [None] * max_slots
        self.queue: deque[Request] = deque()
        self.finished: list[Request] = []
        self.steps = 0
        self.h2d = {"prefill": H2DCount(), "decode": H2DCount()}
        self._h2d_last = (0, 0)  # the latest launch's (copies, bytes)
        self.ahead = AheadCount()
        # the decode launch staged ahead in the last step, read in the next
        self._unread: _DecodeLaunch | None = None
        # decode_fn/prefill_fn let N engines of one model share a single
        # compiled step (the bridge runs many tenant engines of the same
        # architecture; each call still passes its own donated cache). A
        # caller-supplied decode_fn must match the engine's sampling mode
        # (use compile_decode(model, sampling=...)).
        self._decode = decode_fn or ServingEngine.compile_decode(
            model, sampling=sampling, sample_backend=sample_backend)
        self._prefill = prefill_fn or ServingEngine.compile_prefill(model)
        # launch observer: called with every launch descriptor *after* it
        # goes through the executor — the seam ``repro.bridge`` taps to
        # mirror the real decode launch stream into cluster LaunchRequests
        # without perturbing the compute (observation only, no reply)
        self.on_launch = on_launch
        # scheduled launch path: the executor owns the staging ring (depth
        # launches in flight) and the config-state cache — one context, the
        # engine is one tenant of its device. The cache's elision is an
        # account of unchanged fields; every leaf is still copied.
        # sync on the per-launch payload (sampled ids / logits / prefill
        # probe): the KV cache is donated launch-to-launch, so only the
        # per-step output is safe to block on
        self.executor = ScheduledExecutor(self._device_fn, depth=launch_depth,
                                          tenant="engine",
                                          sync_fn=lambda out: out[1])
        self.config_cache = self.executor.cache

    def _device_fn(self, state, desc):
        """One launch from a cached descriptor. Three launch kinds share the
        path: chunked prefill (keyed by ``prefill_tokens``), fused decode
        (device-resident token ring + host overrides → sampled ids), and
        host-sampling decode (``tokens`` field → full logits)."""
        params, cache = state
        if "prefill_tokens" in desc:
            args = self._to_device("prefill", desc["prefill_tokens"],
                                   desc["positions"], desc["prefill_len"],
                                   desc["slot_mask"])
            with TraceAnnotation("serving.dispatch"):
                probe, cache = self._prefill(params, cache, *args)
            return (params, cache), probe
        if self.sampling == "fused":
            args = self._to_device("decode", desc["token_overrides"],
                                   desc["override_mask"], desc["positions"],
                                   desc["live_mask"])
            with TraceAnnotation("serving.dispatch"):
                ids, cache = self._decode(params, cache, self._dev_tokens, *args)
            # the read-back starts when the program ends, and needs no
            # program of its own to queue behind a launch staged after it
            ids.copy_to_host_async()
            self._dev_tokens = ids  # loopback: next launch's input tokens
            return (params, cache), ids
        args = self._to_device("decode", desc["tokens"], desc["positions"],
                               desc["live_mask"])
        with TraceAnnotation("serving.dispatch"):
            logits, cache = self._decode(params, cache, *args)
        return (params, cache), logits

    def _to_device(self, kind: str, *leaves) -> tuple:
        """Copy one launch's numpy leaves to the device, counted in
        ``h2d[kind]``."""
        with TraceAnnotation("serving.h2d"):
            out = tuple(jnp.asarray(x) for x in leaves)
        copies, nbytes = len(leaves), sum(x.nbytes for x in leaves)
        c = self.h2d[kind]
        c.launches, c.copies, c.bytes = (c.launches + 1, c.copies + copies,
                                         c.bytes + nbytes)
        self._h2d_last = (copies, nbytes)
        return out

    def _launch(self, desc: dict):
        """Stage one launch through the executor; adopts the new KV cache
        and returns the (possibly still in-flight) per-launch payload."""
        (_, self.cache), out = self.executor.launch(
            (self.params, self.cache), desc
        )
        if self.on_launch is not None:
            self.on_launch(desc)
        return out

    @staticmethod
    def compile_decode(model, sampling: str = "fused",
                       sample_backend: str = "xla"):
        """One compiled decode step, shareable across every engine of the
        same architecture (`decode_fn=`): N bridged tenant engines then pay
        a single JIT compilation instead of N. ``sampling="fused"`` returns
        the fused decode+sample step (ids out); ``"host"`` the classic
        logits-returning step. Must match the engines' ``sampling=``.
        The fused program is named ``jit_decode_and_sample`` in a profile."""
        if sampling == "fused":
            def decode_and_sample(*args):
                return model.decode_and_sample(*args,
                                               sample_backend=sample_backend)

            return jax.jit(decode_and_sample, donate_argnums=(1,))
        return jax.jit(model.decode_step, donate_argnums=(1,))

    @staticmethod
    def compile_prefill(model):
        """One compiled chunked-prefill launch (`prefill_fn=`), shareable
        like :meth:`compile_decode` (one shape per chunk size)."""
        return jax.jit(model.prefill_chunk, donate_argnums=(1,))

    # ---------------------------------------------------------------- admin

    def submit(self, req: Request) -> None:
        """Queue a request. Rejects prompts the slot layout cannot hold:
        an empty prompt has no token to start decode from, and a prompt of
        ``max_len`` or more would overrun the slot's KV rows before the
        first generated token."""
        if not req.prompt:
            raise ValueError(f"request {req.uid}: empty prompt")
        if len(req.prompt) >= self.max_len:
            raise ValueError(
                f"request {req.uid}: prompt of {len(req.prompt)} tokens "
                f"needs max_len > {len(req.prompt)} (engine max_len="
                f"{self.max_len}) — it would overrun the KV cache")
        req.submitted_at = time.perf_counter()
        self.queue.append(req)

    @property
    def live_slots(self) -> list[int]:
        return [i for i, r in enumerate(self.slot_req) if r is not None]

    def _admit(self) -> None:
        for slot in range(self.max_slots):
            if self.slot_req[slot] is not None or not self.queue:
                continue
            with TraceAnnotation("serving.admit") as span:
                req = self.queue.popleft()
                if span.is_enabled():
                    span.set_metadata(**self._admit_args(req, slot))
                self.slot_req[slot] = req
                self.positions[slot] = 0
                # chunked prefill: all prompt tokens but the last stream
                # through masked launches that advance only this slot;
                # launches stay staged in the executor's ring (no sync),
                # overlapping host descriptor prep with device work
                ptoks = req.prompt[:-1]
                for start in range(0, len(ptoks), self.prefill_chunk):
                    self._prefill_launch(slot, ptoks[start:start + self.prefill_chunk])
                # the prompt's last token seeds the first decode step
                self._set_token(slot, req.prompt[-1])

    def _admit_args(self, req: Request, slot: int) -> dict:
        return {"uid": req.uid, "slot": slot, "prompt_tokens": len(req.prompt),
                "queued_us": (time.perf_counter() - req.submitted_at) * 1e6}

    def _launch_args(self, desc: dict | None = None) -> dict:
        """The latest launch's copies; for a decode launch, given its
        descriptor, also its slot occupancy and the tokens of context it
        attends over."""
        copies, nbytes = self._h2d_last
        args = {"h2d_copies": copies, "h2d_bytes": nbytes}
        if desc is not None:
            live = desc["live_mask"]
            args.update(live=int(live.sum()),
                        context_tokens=int((desc["positions"][live] + 1).sum()))
        return args

    def _prefill_launch(self, slot: int, chunk: list[int]) -> None:
        with TraceAnnotation("serving.prefill_launch") as span:
            n = len(chunk)
            buf = np.zeros((self.prefill_chunk,), np.int32)
            buf[:n] = chunk
            mask = np.zeros((self.max_slots,), bool)
            mask[slot] = True
            self._launch({
                "prefill_tokens": buf,
                "prefill_len": np.int32(n),
                "positions": self.positions.copy(),
                "slot_mask": mask,
                **self._invariant_fields(),
            })
            if span.is_enabled():
                span.set_metadata(path=self.model.prefill_path, valid_tokens=n,
                                  **self._launch_args())
        self.positions[slot] += n

    def _set_token(self, slot: int, tok: int) -> None:
        """Point a slot's next decode input at ``tok`` — the host mirror
        always; plus a device override under fused sampling (the only way
        a host token enters the device-resident ring)."""
        self.tokens[slot, 0] = tok
        if self.sampling == "fused":
            self._overrides[slot] = tok
            self._override_mask[slot] = True

    # ----------------------------------------------------------------- step

    def step(self) -> int:
        """One continuous-batching step; returns the tokens it read. It
        admits what fits, reads one decode launch (its own, or the one the
        last step staged ahead) and, where the next launch is fixed without
        those ids, stages that launch before the read."""
        with StepTraceAnnotation("serving.step", step_num=self.steps) as span:
            self.steps += 1
            produced, finished = self._step()
            if span.is_enabled():
                span.set_metadata(produced=produced, finished=finished)
        return produced

    def _step(self) -> tuple[int, int]:
        """``step``'s work; returns the tokens produced and the requests
        finished."""
        self._admit()
        launch, self._unread = self._unread, None
        if launch is None:
            live = self.live_slots
            if not live:
                return 0, 0
            launch = self._decode_launch(live)
        if self._stages_after(launch):
            self._unread = self._decode_launch([s for s, _, _ in launch.rows],
                                               ahead=True)
        return self._read(launch)

    def _stages_after(self, launch: _DecodeLaunch) -> bool:
        """Whether the decode launch after ``launch`` is fixed without its
        ids: fused sampling, no request waiting, every slot still holding
        the request ``launch`` decoded for, and none of them receiving its
        last token by length in ``launch``."""
        return (self.sampling == "fused" and not self.queue
                and len(launch.rows) == self.max_slots
                and all(self.slot_req[s] is req and not last
                        for s, req, last in launch.rows))

    def _decode_launch(self, live: list[int], ahead: bool = False) -> _DecodeLaunch:
        """Dispatch one decode launch over ``live``; its ids stay unread.
        Each live slot's position advances by one, so the next launch can
        be built before these ids are read."""
        with TraceAnnotation("serving.decode_launch") as span:
            desc = self._decode_descriptor(live)
            out = self._launch(desc)
            if self.sampling == "fused":
                self._override_mask[:] = False  # consumed by the staged launch
            self.positions[live] += 1
            self.ahead.launches += ahead
            if span.is_enabled():
                span.set_metadata(ahead=ahead, **self._launch_args(desc))
        return _DecodeLaunch(out, [(s, self.slot_req[s], self._last_by_length(s))
                                   for s in live])

    def _last_by_length(self, slot: int) -> bool:
        """Whether the decode launch just dispatched holds the last token
        that the slot's request may have by length: its ``max_new_tokens``
        th, or the one at the last row of the slot."""
        req, pos = self.slot_req[slot], int(self.positions[slot])
        return pos - len(req.prompt) + 1 >= req.max_new_tokens or pos >= self.max_len - 1

    def _read(self, launch: _DecodeLaunch) -> tuple[int, int]:
        """Read one decode launch's ids and retire them against the
        requests it was launched for."""
        # sampling is the synchronization point. Fused: the launch already
        # sampled on-device and its ids are on their way to the host — block
        # on (B,) ids, a few bytes. Host: argmax here needs the full
        # (B, vocab) logits across the boundary first.
        with TraceAnnotation("serving.sync") as span:
            if self.sampling == "fused":
                nxt = np.asarray(launch.out)[:, 0]
            else:
                nxt = np.asarray(jnp.argmax(launch.out[:, 0], axis=-1), np.int32)
            if span.is_enabled():
                span.set_metadata(d2h_bytes=nxt.nbytes)
        with TraceAnnotation("serving.retire"):
            produced = finished = 0
            for slot, req, last in launch.rows:
                if req.done:
                    # decoded past the EOS that the launch before sampled:
                    # the row goes to no request, and the slot's next
                    # occupant starts from its own prefill and override
                    self.ahead.dropped_rows += 1
                    continue
                tok = int(nxt[slot])
                req.generated.append(tok)
                produced += 1
                self.tokens[slot, 0] = tok
                if last or (self.eos_id is not None and tok == self.eos_id):
                    req.done = True
                    self.finished.append(req)
                    self.slot_req[slot] = None  # slot freed for the next request
                    self.positions[slot] = 0
                    finished += 1
                    # zero the freed slot's token state: later descriptors
                    # must not carry (or dedup against) the dead request's
                    # last token
                    self._set_token(slot, 0)
        return produced, finished

    def _decode_descriptor(self, live: list[int]) -> dict:
        """The fields that parameterize one decode launch. Copies snapshot
        the mutable host buffers so cached values stay bit-stable. Fused
        sampling has no ``tokens`` leaf: input ids are device-resident, and
        the override pair is all-zero/all-False (elided) except on the step
        after an admission or a free."""
        mask = np.zeros((self.max_slots,), bool)
        mask[live] = True
        desc = {
            "positions": self.positions.copy(),
            "live_mask": mask,
            **self._invariant_fields(),
        }
        if self.sampling == "fused":
            desc["token_overrides"] = self._overrides.copy()
            desc["override_mask"] = self._override_mask.copy()
        else:
            desc["tokens"] = self.tokens.copy()
        return desc

    def _invariant_fields(self) -> dict:
        """Sampling/shape config common to every launch kind — sent once,
        device-resident (elided) afterwards."""
        return {
            "max_len": np.int32(self.max_len),
            "eos_id": np.int32(-1 if self.eos_id is None else self.eos_id),
            "n_slots": np.int32(self.max_slots),
        }

    @property
    def sync_bytes(self) -> int:
        """Device→host bytes the host blocks on per decode step — the
        sampling synchronization the closed-loop driver prices on the
        feedback edge. Fused sampling returns ``(B, 1)`` int32 ids; host
        sampling pulls the full ``(B, vocab)`` logits across the boundary
        just to argmax them."""
        if self.sampling == "fused":
            return self.max_slots * 4
        from repro.models.layers import COMPUTE_DTYPE
        vocab = self.model.cfg.vocab_size
        return self.max_slots * vocab * np.dtype(COMPUTE_DTYPE).itemsize

    def config_traffic(self) -> dict[str, float]:
        """Descriptor bytes whose values changed since the previous launch
        ("sent") vs. unchanged ("elided"), across all launches so far
        (prefill and batch decode alike), as ``ConfigStateCache`` counts
        them. An account, not the copy: the launches still copy "elided"
        leaves. The copies they make are in ``h2d`` and in each launch
        span's ``h2d_bytes`` argument."""
        s = self.config_cache.stats
        return {
            "bytes_sent": float(s.bytes_sent),
            "bytes_elided": float(s.bytes_elided),
            "elision_ratio": s.elision_ratio,
        }

    @property
    def idle(self) -> bool:
        """No request queued or live, and no decode launch left unread."""
        return not (self.queue or self.live_slots or self._unread is not None)

    def run_until_done(self, max_steps: int = 10_000) -> list[Request]:
        steps = 0
        while not self.idle and steps < max_steps:
            self.step()
            steps += 1
        self.executor.drain()  # retire any still-staged launches
        return self.finished
