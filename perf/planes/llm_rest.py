"""LLM plane: `cli microservice PerfLLM REST`, driven over POST /v1/generate
(SSE where the cell streams).  The tokenizer is the program's byte tokenizer
(one token per ASCII byte, no BOS: servers/llmserver.py ByteTokenizer.encode),
so a prompt of n lower-case letters is n tokens.
"""

from __future__ import annotations

import asyncio
import json
import os

import aiohttp
import numpy as np

PROBE_SEED = 1234


def letters(rng: np.random.Generator, n: int) -> str:
    return rng.integers(97, 123, size=n, dtype=np.uint8).tobytes().decode("ascii")


class Plane:
    def __init__(self, run):
        self.run = run
        self.port = run.free_port()
        self.url = f"http://127.0.0.1:{self.port}"
        self.vocab = int(run.config["vocab_size"])
        self.stream = bool(run.cell["traffic"].get("stream", False))
        self.violations: list = []
        self.session = None
        self._probe = None

    # -- launch ----------------------------------------------------------
    def prepare(self) -> None:
        cfg = self.run.config
        kwargs = dict(cfg["server"])
        kwargs["model_kwargs"] = {
            ours: cfg[theirs] for ours, theirs in cfg["model_kwargs_from"].items()}
        kwargs["seed"] = self.run.seed
        self.kwargs_path = os.path.join(self.run.out_dir, "llm_kwargs.json")
        with open(self.kwargs_path, "w") as f:
            json.dump(kwargs, f)

    def command(self) -> dict:
        return {
            "argv": ["microservice", "PerfLLM", "REST", "--port", str(self.port),
                     "--host", "127.0.0.1"],
            "cwd": os.path.join(os.path.dirname(os.path.abspath(__file__)), "serve"),
            "env": {"PERF_LLM_KWARGS": self.kwargs_path},
            "ready": self.url + "/ready",
        }

    async def connect(self) -> None:
        self.session = aiohttp.ClientSession(
            connector=aiohttp.TCPConnector(limit=0),
            timeout=aiohttp.ClientTimeout(total=None))

    async def close(self) -> None:
        if self.session is not None:
            await self.session.close()

    # -- requests --------------------------------------------------------
    def make_request(self, sizes: dict, rng: np.random.Generator) -> dict:
        """sizes: prompt_tokens, output_tokens.  No two prompts share a prefix."""
        body = {"prompt": letters(rng, sizes["prompt_tokens"]),
                "max_new_tokens": sizes["output_tokens"]}
        if self.stream:
            body["stream"] = True
        return {"body": json.dumps(body).encode(), "tokens": sizes["output_tokens"],
                "stream": self.stream}

    def work(self, sizes: dict) -> float:
        return float(sizes["prompt_tokens"] + sizes["output_tokens"])

    def samples(self, rec: dict) -> dict:
        out = {"latency_s": rec["done"] - rec["due"]}
        if rec.get("first") is not None:
            out["ttft_s"] = rec["first"] - rec["due"]
            out["ttft_from_send_s"] = rec["first"] - rec["sent"]
            if rec["n_tokens"] > 1:
                out["tpot_s"] = (rec["last"] - rec["first"]) / (rec["n_tokens"] - 1)
        return out

    def _check(self, reply: dict, want: int) -> str | None:
        tokens = reply.get("tokens")
        if not isinstance(tokens, list) or len(tokens) != want:
            return f"{len(tokens or [])} tokens, wanted {want}"
        if not all(isinstance(t, int) and 0 <= t < self.vocab for t in tokens):
            return f"token id outside [0, {self.vocab})"
        if "truncated_prompt" in reply:
            return f"truncated prompt: {reply['truncated_prompt']}"
        return None

    async def send(self, request: dict) -> dict:
        """ok is False for a transport failure or a refusal; a reply that
        arrives but breaks a check is a violation, which makes the run
        incorrect."""
        loop = asyncio.get_running_loop()
        out = {"ok": False, "first": None, "last": None, "n_tokens": 0, "gaps": []}
        try:
            async with self.session.post(self.url + "/v1/generate",
                                         data=request["body"]) as resp:
                if resp.status != 200:
                    out["error"] = f"HTTP {resp.status}"
                    return out
                if not request["stream"]:
                    reply = await resp.json(content_type=None)
                else:
                    reply, streamed = None, []
                    async for line in resp.content:
                        if not line.startswith(b"data: "):
                            continue
                        now = loop.time()
                        event = json.loads(line[6:])
                        if "token" in event:
                            if out["first"] is None:
                                out["first"] = now
                            else:
                                out["gaps"].append(now - out["last"])
                            out["last"] = now
                            streamed.append(event["token"])
                        elif event.get("done"):
                            reply = event
                        elif "error" in event:
                            out["error"] = str(event["error"])
                            return out
                    if reply is None:
                        out["error"] = "stream ended without a done event"
                        return out
                    if streamed != reply.get("tokens"):
                        self.violations.append("streamed tokens differ from the done event's")
        except (aiohttp.ClientError, asyncio.TimeoutError, ConnectionError) as e:
            out["error"] = f"{type(e).__name__}: {e}"
            return out
        bad = self._check(reply, request["tokens"])
        if bad:
            self.violations.append(bad)
        out["ok"] = True
        out["n_tokens"] = len(reply.get("tokens") or [])
        out["reply_tokens"] = reply.get("tokens")
        return out

    # -- warm-up and correctness, outside the window -----------------------
    async def _ask(self, prompt: str, n_new: int, stream: bool, seed=None) -> list:
        body = {"prompt": prompt, "max_new_tokens": n_new}
        if stream:
            body["stream"] = True
        if seed is not None:
            body["seed"] = seed
        rec = await self.send({"body": json.dumps(body).encode(), "tokens": n_new,
                               "stream": stream})
        if not rec["ok"]:
            raise RuntimeError(f"probe request failed: {rec.get('error')}")
        return rec["reply_tokens"]

    async def probe(self, phase: str, rng: np.random.Generator) -> None:
        """before: one request per shape the window will use (the cell's
        `warmup` list), then the seeded probe as a plain reply and as a stream,
        which must agree.  after: the seeded probe again, which must repeat the
        first token for token."""
        cell = self.run.cell
        spec = cell["probe"]
        if phase == "before":
            for w in cell["warmup"]:
                await self._ask(letters(rng, w["prompt_tokens"]), w["output_tokens"],
                                self.stream)
            self._probe_prompt = letters(rng, spec["prompt_tokens"])
            self._probe = await self._ask(self._probe_prompt, spec["output_tokens"],
                                          False, PROBE_SEED)
            streamed = await self._ask(self._probe_prompt, spec["output_tokens"],
                                       True, PROBE_SEED)
            if streamed != self._probe:
                self.violations.append("seeded SSE tokens differ from the plain reply's")
        else:
            again = await self._ask(self._probe_prompt, spec["output_tokens"],
                                    False, PROBE_SEED)
            if again != self._probe:
                self.violations.append(
                    "the seeded probe gave other tokens after the window than before")

    # -- what readers read -------------------------------------------------
    async def scrape(self) -> dict:
        out = {}
        async with self.session.get(self.url + "/metrics") as resp:
            out["metrics"] = await resp.text()
        async with self.session.get(self.url + "/debug/timeline?n=64") as resp:
            out["timelines"] = (await resp.json(content_type=None)).get("timelines", [])
        return out
