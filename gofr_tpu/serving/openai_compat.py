"""OpenAI-compatible serving surface: /v1/chat/completions,
/v1/completions, /v1/models over the continuous-batching engine.

The de-facto standard client protocol: anything that speaks the OpenAI
API (SDKs, proxies, eval harnesses) points at this app unchanged.

    app.post("/v1/chat/completions", oa.chat_completions)
    ... or in one line:
    install_openai_routes(app, engine, tokenizer, model="llama-3.2-1b")

Covered request surface: ``messages``/``prompt``, ``max_tokens`` (and
``max_completion_tokens``), ``temperature``, ``top_p``, ``stream``,
``stop`` (up to 4 stop sequences, enforced host-side with the matched
text trimmed and the engine request cancelled), ``user`` (ignored),
``n`` (only 1 — a 400 otherwise, honestly). Responses carry the
standard envelope: ``chat.completion`` / ``text_completion`` objects,
``chatcmpl-``/``cmpl-`` ids, ``finish_reason`` ("stop" for eos/stop
sequence, "length" for the token budget), and token ``usage``.
Streaming is SSE with ``chat.completion.chunk`` deltas (role chunk
first, content chunks after, terminal chunk with finish_reason, then
``data: [DONE]``); engine failures surface as an ``error`` event, never
a clean-looking truncation.
"""

from __future__ import annotations

import json
import secrets
import time
from typing import Any

from ..http.errors import HTTPError
from ..http.response import Raw, Stream
from .engine import Engine, SamplingParams


class _OpenAIError(HTTPError):
    """Renders through the framework's ``{"error": {...}}`` envelope
    with OpenAI's type/param carried in ``details`` — clients key on
    the status code and ``error.message``, which match exactly."""

    def __init__(self, message: str, *, status: int = 400,
                 err_type: str = "invalid_request_error",
                 param: str | None = None) -> None:
        super().__init__(message, status_code=status,
                         details={"type": err_type, "param": param})


def _content_text(content: Any) -> str:
    """Message content: a string, or the documented content-parts form
    ``[{"type": "text", "text": ...}, ...]`` (text parts concatenated;
    non-text parts rejected — no vision here)."""
    if isinstance(content, str):
        return content
    if isinstance(content, list):
        texts = []
        for part in content:
            if not isinstance(part, dict) or part.get("type") != "text" \
                    or not isinstance(part.get("text"), str):
                raise _OpenAIError(
                    "only text content parts are supported",
                    param="messages")
            texts.append(part["text"])
        return "".join(texts)
    raise _OpenAIError("message content must be a string or text parts",
                       param="messages")


def _render_messages(messages: list) -> str:
    """Chat template: the simple role-tagged transcript (model-agnostic
    — random-weight bench models have no canonical template; swap in a
    real template via the ``render`` hook for released checkpoints)."""
    parts = []
    for m in messages:
        if not isinstance(m, dict) or "content" not in m:
            raise _OpenAIError("each message needs role and content",
                               param="messages")
        parts.append(f"{m.get('role', 'user')}: "
                     f"{_content_text(m['content'])}")
    parts.append("assistant:")
    return "\n".join(parts)


def _opt(body: dict, key: str, default):
    """OpenAI treats an explicit JSON null like an absent optional."""
    value = body.get(key, default)
    return default if value is None else value


def _params_from(body: dict) -> SamplingParams:
    max_new = _opt(body, "max_completion_tokens",
                   _opt(body, "max_tokens", 128))
    try:
        params = SamplingParams(
            temperature=float(_opt(body, "temperature", 1.0)),
            top_p=float(_opt(body, "top_p", 1.0)),
            max_new_tokens=int(max_new))
        n = int(_opt(body, "n", 1))
    except (TypeError, ValueError) as exc:
        raise _OpenAIError("temperature/top_p/max_tokens/n must be "
                           "numbers", param="max_tokens") from exc
    if not 1 <= params.max_new_tokens <= 4096:
        raise _OpenAIError("max_tokens out of range [1, 4096]",
                           param="max_tokens")
    if n != 1:
        raise _OpenAIError("only n=1 is supported", param="n")
    return params


def _stops_from(body: dict) -> list[str]:
    stop = body.get("stop")
    if stop is None:
        return []
    if isinstance(stop, str):
        stop = [stop]
    if not isinstance(stop, list) or len(stop) > 4 \
            or not all(isinstance(s, str) and s for s in stop):
        raise _OpenAIError("stop must be a string or up to 4 strings",
                           param="stop")
    return stop


def _cut_at_stop(text: str, stops: list[str]) -> tuple[str, bool]:
    """Trim at the earliest stop-sequence match; True when one hit."""
    cut = -1
    for s in stops:
        i = text.find(s)
        if i >= 0 and (cut < 0 or i < cut):
            cut = i
    return (text[:cut], True) if cut >= 0 else (text, False)


class OpenAIRoutes:
    def __init__(self, engine: Engine, tokenizer: Any, *,
                 model: str = "gofr-tpu", render=None) -> None:
        self.engine = engine
        self.tokenizer = tokenizer
        self.model = model
        self.render = render or _render_messages

    # ------------------------------------------------------------- models
    def models(self, ctx) -> Any:
        return Raw({"object": "list",
                    "data": [{"id": self.model, "object": "model",
                              "owned_by": "gofr-tpu"}]})

    # -------------------------------------------------------------- chat
    @staticmethod
    def _tenant_of(ctx) -> str | None:
        """Auth principal -> accounting label (same resolver as the
        native /chat path); usage metering works for OpenAI clients
        authenticated with API keys / JWTs like any other route."""
        resolver = getattr(ctx.container, "tenant_resolver", None)
        return resolver.resolve(ctx.auth_info) if resolver else None

    async def chat_completions(self, ctx) -> Any:
        body = ctx.bind() or {}
        messages = body.get("messages")
        if not messages or not isinstance(messages, list):
            raise _OpenAIError("messages required", param="messages")
        prompt = self.render(messages)
        return await self._complete(body, prompt, chat=True,
                                    tenant=self._tenant_of(ctx))

    async def completions(self, ctx) -> Any:
        body = ctx.bind() or {}
        prompt = body.get("prompt")
        if isinstance(prompt, list):  # the API allows a list of one
            prompt = prompt[0] if prompt else None
        if not prompt or not isinstance(prompt, str):
            raise _OpenAIError("prompt required", param="prompt")
        return await self._complete(body, prompt, chat=False,
                                    tenant=self._tenant_of(ctx))

    # ------------------------------------------------------------ engine
    async def _complete(self, body: dict, prompt: str, *,
                        chat: bool, tenant: str | None = None) -> Any:
        params = _params_from(body)
        stops = _stops_from(body)
        prompt_tokens = self.tokenizer.encode(prompt)
        req = self.engine.submit(prompt_tokens, params, tenant=tenant)
        if req.error:
            # typed scheduler rejects map to OpenAI's error types: rate
            # limits are 429 rate_limit_error with Retry-After, the
            # rest stay 503 server_error
            rej = getattr(req, "reject", None)
            if rej is not None:
                from .scheduler import retry_after_header
                err = _OpenAIError(
                    req.error,
                    status=429 if rej.code == "rate_limited" else 503,
                    err_type="rate_limit_error"
                    if rej.code == "rate_limited" else "server_error")
                err.headers.update(retry_after_header(rej))
                raise err
            raise _OpenAIError(req.error, status=503,
                               err_type="server_error")
        oid = (("chatcmpl-" if chat else "cmpl-")
               + secrets.token_hex(12))
        created = int(time.time())
        if body.get("stream"):
            return Stream(self._sse(req, oid, created, stops, chat))

        tokens: list[int] = []
        stopped = False
        try:
            while True:
                token = await req.out_queue.get()
                if token is None:
                    break
                tokens.append(token)
                if stops:
                    # enforce stop sequences WHILE draining: no slot
                    # burns out its full token budget past a match
                    _, stopped = _cut_at_stop(
                        self.tokenizer.decode(tokens), stops)
                    if stopped:
                        break
        finally:
            if req.finished_at is None:
                # disconnect mid-drain or stop-sequence hit: free the
                # decode slot (mirrors the streaming path's aclose)
                self.engine.cancel(req)
        if req.error:
            raise _OpenAIError(f"generation failed: {req.error}",
                               status=500, err_type="server_error")
        text = self.tokenizer.decode(tokens)
        text, _hit = _cut_at_stop(text, stops)
        stopped = stopped or _hit
        finish = "stop" if (stopped or len(tokens)
                            < params.max_new_tokens) else "length"
        choice = ({"index": 0, "message": {"role": "assistant",
                                           "content": text},
                   "finish_reason": finish} if chat else
                  {"index": 0, "text": text, "finish_reason": finish})
        return Raw({
            "id": oid,
            "object": "chat.completion" if chat else "text_completion",
            "created": created, "model": self.model,
            "choices": [choice],
            "usage": {"prompt_tokens": len(prompt_tokens),
                      "completion_tokens": len(tokens),
                      "total_tokens": len(prompt_tokens) + len(tokens)},
        })

    async def _sse(self, req, oid: str, created: int, stops: list[str],
                   chat: bool):
        def chunk(delta: dict | None, finish: str | None = None) -> str:
            if chat:
                c = {"index": 0, "delta": delta or {},
                     "finish_reason": finish}
            else:
                c = {"index": 0, "text": (delta or {}).get("content", ""),
                     "finish_reason": finish}
            return "data: " + json.dumps({
                "id": oid,
                "object": ("chat.completion.chunk" if chat
                           else "text_completion"),
                "created": created, "model": self.model,
                "choices": [c]}) + "\n\n"

        gen = self.engine.stream_request(req)
        # deltas come from re-decoding the WHOLE accumulated token list
        # (not per-token decode, which mangles multi-byte characters
        # split across tokens); a tail of hold chars stays back while
        # it could still begin a stop sequence
        tokens_acc: list[int] = []
        sent = 0
        hold = max((len(s) for s in stops), default=1) - 1
        stopped = False
        try:
            if chat:
                yield chunk({"role": "assistant"})
            async for token in gen:
                tokens_acc.append(token)
                text = self.tokenizer.decode(tokens_acc)
                cut, stopped = _cut_at_stop(text, stops)
                if stopped:
                    if cut[sent:]:
                        yield chunk({"content": cut[sent:]})
                    break
                emit_to = len(text) - hold
                # a token boundary can split a multi-byte character:
                # the dangling bytes decode as U+FFFD now but become a
                # real character once the rest arrives — hold trailing
                # replacements back (legit ones flush at finalize)
                while emit_to > sent and text[emit_to - 1] == "�":
                    emit_to -= 1
                if emit_to > sent:
                    yield chunk({"content": text[sent:emit_to]})
                    sent = emit_to
            if req.error:
                yield ("data: " + json.dumps(
                    {"error": {"message": req.error,
                               "type": "server_error"}}) + "\n\n")
                return
            if not stopped:
                text = self.tokenizer.decode(tokens_acc)
                if text[sent:]:
                    yield chunk({"content": text[sent:]})
            finish = "stop" if (stopped or len(tokens_acc)
                                < req.params.max_new_tokens) else "length"
            yield chunk(None, finish)
            yield "data: [DONE]\n\n"
        finally:
            await gen.aclose()   # disconnect/stop-seq cancels the engine


def install_openai_routes(app: Any, engine: Engine, tokenizer: Any, *,
                          model: str = "gofr-tpu", render=None
                          ) -> OpenAIRoutes:
    """Register the three OpenAI-compatible routes on an App."""
    routes = OpenAIRoutes(engine, tokenizer, model=model, render=render)
    app.post("/v1/chat/completions", routes.chat_completions)
    app.post("/v1/completions", routes.completions)
    app.get("/v1/models", routes.models)
    return routes
