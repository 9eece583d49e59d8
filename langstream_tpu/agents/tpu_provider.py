"""The in-tree TPU ServiceProvider: the point of the whole framework.

Where the reference's providers are HTTP clients
(``OpenAIServiceProvider.java:26``, ``VertexAIProvider.java:58``, …), this
provider hands the AI agents a local :class:`TpuServingEngine` /
:class:`EmbeddingEngine` — completions and embeddings run on this pod's
chips, streaming tokens straight into the agent's chunk writer.

Resource shape (``configuration.yaml``):

    resources:
      - type: "tpu-serving-configuration"
        name: "tpu"
        configuration:
          model: "llama-1b"            # tiny | llama-1b | llama3-8b |
                                       # llama3-70b | moe-8x7b/mixtral-8x7b
          slots: 8
          max-seq-len: 2048
          tokenizer: null              # byte-level fallback; or local HF dir
          checkpoint: null             # local weights dir; random init otherwise
          mesh: {dp: 1, tp: 8}         # omit for single device; `sp` makes
                                       # long prefills sequence-parallel,
                                       # `ep` shards MoE experts
          quantize: "int8"             # weight-only int8 (or null = bf16)
          kv-quantize: null            # "int8": per-row int8 KV cache halves
                                       # decode's cache-read HBM traffic
          kv-block-size: 64            # rows per block of the paged KV pool
          kv-pool-fraction: 0.5        # pool size, of slots x max-seq-len rows
          prefix-cache: true           # shared prompt prefixes skip prefill
          prefill-chunk: 0             # >0: long prompts interleave with decode
          speculative-drafts: 0        # >0: prompt-lookup speculation (greedy)
          decode-chunk: 16             # fused decode steps per dispatch
          decode-chunk-light: 8        # short sequential chunks while active
                                       # slots <= light-load-slots (the TTFT
                                       # regime; 0 = always decode-chunk)
          light-load-slots: null       # default slots // 8
          warmup-on-start: false       # true: pre-compile both chunk regimes
                                       # + padded prefill shapes on the first
                                       # request (serving pods want this)
          embeddings-model: "minilm-l6"
          qos: null                    # multi-tenant QoS scheduler: priority
                                       # classes (WDRR admission), per-tenant
                                       # token buckets, preemptive load
                                       # shedding — docs/SCHEDULING.md; null
                                       # keeps the FIFO admission queue
          wedge-window-s: 60           # engine watchdog: WEDGED (liveness
                                       # probe fails, pod rescheduled) after
                                       # this long with queued work and no
                                       # step progress (serving/health.py)
          slo: null                    # SLO objectives (ttft / queue-wait /
                                       # shed-rate / availability targets)
                                       # tracked with multi-window burn
                                       # rates; `alert` flight events +
                                       # slo_burn_rate gauges on fast burn —
                                       # docs/OBSERVABILITY.md Health & SLO
          streaming: false             # per-chunk token delivery with TBT
                                       # (time-between-tokens) telemetry:
                                       # stream-emit/stall/cancel flight
                                       # events, per-class tbt_seconds
                                       # histograms, stats()["streaming"] —
                                       # off keeps every default surface
                                       # byte-identical
          stream-stall-s: 2.0          # inter-emit gap that counts as a
                                       # stall for classes without a
                                       # tbt-p99-s target
"""

from __future__ import annotations

from typing import Any

from langstream_tpu.agents.services import (
    Chunk,
    CompletionResult,
    CompletionsService,
    EmbeddingsService,
    ServiceProvider,
    StreamingChunksConsumer,
)
from langstream_tpu.serving.engine import (
    EmbeddingEngine,
    ServingConfig,
    TpuServingEngine,
)


def _render_chat_prompt(messages: list[dict[str, str]]) -> str:
    """Default chat template (checkpoint-specific templates come from the
    tokenizer when a real HF tokenizer dir is configured)."""
    parts = []
    for m in messages:
        parts.append(f"<|{m.get('role', 'user')}|>\n{m.get('content', '')}")
    parts.append("<|assistant|>\n")
    return "\n".join(parts)


class _StreamAdapter:
    """Bridges engine on_token callbacks to the agents' chunk consumers,
    detokenising incrementally (only complete UTF-8 prefixes are emitted).
    Stop sequences are excluded from the stream: text that could still
    grow into a stop match is held back, and a match truncates the stream
    at its start (mirroring the engine's final-text truncation)."""

    def __init__(self, tokenizer, consumer: StreamingChunksConsumer,
                 stop: list[str] | None = None):
        from langstream_tpu.serving.engine import _normalize_stop

        self.tokenizer = tokenizer
        self.consumer = consumer
        self.stop = _normalize_stop(stop)
        self.ids: list[int] = []
        self.emitted = ""
        self.index = 0
        self.closed = False

    def _stop_holdback(self, text: str) -> int:
        """Chars at the end of ``text`` that are a prefix of some stop
        string — unsafe to emit until the match resolves either way."""
        hold = 0
        for s in self.stop:
            for k in range(min(len(s) - 1, len(text)), 0, -1):
                if s.startswith(text[-k:]):
                    hold = max(hold, k)
                    break
        return hold

    async def on_token(self, token: int, logprob: float, last: bool) -> None:
        if self.closed:
            return
        self.ids.append(token)
        text = self.tokenizer.decode(self.ids)
        # hold back a trailing replacement char (partial multi-byte sequence)
        safe = text[:-1] if text.endswith("�") and not last else text
        if self.stop:
            hits = [i for i in (safe.find(s) for s in self.stop) if i >= 0]
            if hits:
                safe = safe[: min(hits)]
                last = True
            elif not last:
                safe = safe[: len(safe) - self._stop_holdback(safe)]
        delta = safe[len(self.emitted):]
        if delta or last:
            self.emitted = safe
            self.closed = last
            result = self.consumer(Chunk(delta, self.index, last=last))
            if hasattr(result, "__await__"):
                await result
            self.index += 1


class _ChunkAdapter:
    """Bridges engine on_chunk callbacks to the agents' chunk consumers.

    The streaming-configured engine already detokenised the delta,
    held back partial UTF-8 sequences and possible stop-prefix tails,
    and truncated at stop matches (``_stream_text``) — so this adapter
    only re-shapes ``(new_ids, new_text, is_final)`` into :class:`Chunk`
    calls. Using on_chunk instead of on_token is what feeds the engine's
    TBT telemetry: each delivery is timestamped at the decode-chunk
    safe point and lands in the inter-token-interval digest."""

    def __init__(self, consumer: StreamingChunksConsumer):
        self.consumer = consumer
        self.index = 0

    async def on_chunk(self, new_ids: list, new_text: str, is_final: bool) -> None:
        result = self.consumer(Chunk(new_text, self.index, last=is_final))
        if hasattr(result, "__await__"):
            await result
        self.index += 1


class TpuCompletionsService(CompletionsService):
    def __init__(self, engine: TpuServingEngine):
        self.engine = engine

    async def _generate(
        self,
        prompt: str,
        options: dict[str, Any],
        consumer: StreamingChunksConsumer | None,
    ) -> CompletionResult:
        if consumer is not None and self.engine.config.streaming:
            # streaming-configured engine: deliver at the chunk safe
            # point (TBT-instrumented); the engine does the holdback
            result = await self.engine.generate(
                prompt,
                options,
                on_chunk=_ChunkAdapter(consumer).on_chunk,
            )
            return CompletionResult(
                text=result["text"],
                num_prompt_tokens=result["num_prompt_tokens"],
                num_completion_tokens=result["num_completion_tokens"],
                finish_reason=result["finish_reason"],
                ttft_s=result.get("ttft", 0.0),
                queue_wait_s=result.get("queue_wait", 0.0),
                prefill_s=result.get("prefill", 0.0),
            )
        adapter = (
            _StreamAdapter(
                self.engine.tokenizer, consumer, stop=options.get("stop")
            )
            if consumer is not None
            else None
        )
        result = await self.engine.generate(
            prompt,
            options,
            on_token=adapter.on_token if adapter else None,
        )
        return CompletionResult(
            text=result["text"],
            num_prompt_tokens=result["num_prompt_tokens"],
            num_completion_tokens=result["num_completion_tokens"],
            finish_reason=result["finish_reason"],
            ttft_s=result.get("ttft", 0.0),
            queue_wait_s=result.get("queue_wait", 0.0),
            prefill_s=result.get("prefill", 0.0),
        )

    async def chat_completions(
        self,
        messages: list[dict[str, str]],
        options: dict[str, Any],
        consumer: StreamingChunksConsumer | None = None,
    ) -> CompletionResult:
        return await self._generate(_render_chat_prompt(messages), options, consumer)

    async def text_completions(
        self,
        prompt: str,
        options: dict[str, Any],
        consumer: StreamingChunksConsumer | None = None,
    ) -> CompletionResult:
        return await self._generate(prompt, options, consumer)


class TpuEmbeddingsService(EmbeddingsService):
    def __init__(self, engine: EmbeddingEngine):
        self.engine = engine

    async def compute_embeddings(self, texts: list[str]) -> list[list[float]]:
        return await self.engine.embed(texts)


class TpuServiceProvider(ServiceProvider):
    def __init__(self, resource_config: dict[str, Any]):
        self.resource_config = resource_config

    def _engine_config(self) -> dict[str, Any]:
        """Engine topology comes from the *resource* (model, slots, mesh,
        checkpoint); per-request options (max-tokens, temperature, …) come
        from the agent at call time — so every agent in the app shares one
        engine per resource."""
        return {
            k: v
            for k, v in self.resource_config.items()
            if k not in ("type", "name")
        }

    def get_completions_service(self, config: dict[str, Any]) -> CompletionsService:
        engine = TpuServingEngine.get_or_create(
            ServingConfig.from_dict(self._engine_config())
        )
        return TpuCompletionsService(engine)

    def get_embeddings_service(self, config: dict[str, Any]) -> EmbeddingsService:
        cfg = self._engine_config()
        engine = EmbeddingEngine.get_or_create(
            model=cfg.get("embeddings-model", "minilm-l6"),
            tokenizer=cfg.get("tokenizer"),
            checkpoint=cfg.get("embeddings-checkpoint"),
            mesh=cfg.get("mesh"),
        )
        return TpuEmbeddingsService(engine)
