"""LLM plane with a logit-level reference check: planes/llm_rest.py as it is,
plus, before the window, the seeded probe asked once more with "logits": true
(the float32 logits each of its tokens was sampled from, out of the step
programs that serve every request) and compared with the configuration's
plain reference (perf/reference/<...>.py) on the same weights: prefill then
decode through the pool against the reference's full forward over the prompt
and the tokens the server chose.  `correct` is false on a violation.

The weights are drawn from the configuration's `weights_seed` in every run (the
deployment's weights; the run's --seed draws the traffic and the probe).

The reference is a helper child on the host's CPU, started with the server so
that building its weights hides behind the server's own start; what it still
adds to set-up is printed.
"""

from __future__ import annotations

import asyncio
import base64
import json
import os
import subprocess
import sys
import time

import numpy as np

from planes import llm_rest
from server import child_env


class Plane(llm_rest.Plane):
    def prepare(self) -> None:
        super().prepare()
        with open(self.kwargs_path) as f:
            kwargs = json.load(f)
        kwargs["seed"] = self.run.config["weights_seed"]   # the configuration's, not the run's
        with open(self.kwargs_path, "w") as f:
            json.dump(kwargs, f)
        out = self.run.out_dir
        self.ask_path = os.path.join(out, "reference_ask.json")
        self.answer_path = os.path.join(out, "reference_answer.npz")
        self.reference = subprocess.Popen(
            [sys.executable, os.path.join(self.run.perf_dir, self.run.config["reference"]),
             self.kwargs_path, self.ask_path, self.answer_path],
            cwd=self.run.repo, env=child_env("cpu", False, {}),
            stdout=subprocess.DEVNULL,
            stderr=open(os.path.join(out, "reference.log"), "ab"))

    async def close(self) -> None:
        await super().close()
        if self.reference.poll() is None:
            self.reference.kill()
            self.reference.wait()

    async def probe(self, phase: str, rng: np.random.Generator) -> None:
        await super().probe(phase, rng)
        if phase == "before":
            await self._against_reference()

    async def _served_logits(self) -> np.ndarray:
        spec = self.run.cell["probe"]
        body = {"prompt": self._probe_prompt, "max_new_tokens": spec["output_tokens"],
                "seed": llm_rest.PROBE_SEED, "logits": True}
        async with self.session.post(self.url + "/v1/generate",
                                     data=json.dumps(body).encode()) as resp:
            if resp.status != 200:
                raise RuntimeError(f"logits probe failed: HTTP {resp.status} "
                                   f"{(await resp.text())[:300]}")
            reply = await resp.json(content_type=None)
        if reply.get("tokens") != self._probe:
            self.violations.append("the seeded probe gave other tokens when asked for logits")
        packed = reply["logits"]
        return np.frombuffer(base64.b64decode(packed["base64"]),
                             "<f4").reshape(packed["shape"])

    def _wait_for_answer(self) -> dict:
        while not os.path.exists(self.answer_path):
            if self.reference.poll() is not None:
                raise RuntimeError("the float32 reference failed; see reference.log")
            time.sleep(0.1)
        with np.load(self.answer_path) as answer:
            return {k: answer[k] for k in answer.files}

    async def _against_reference(self) -> None:
        served = await self._served_logits()
        prompt = [ord(c) for c in self._probe_prompt]   # the byte tokenizer's ids
        ask = {"tokens": prompt + self._probe,
               "rows": [len(prompt) - 1, len(prompt) - 1 + len(self._probe)]}
        with open(self.ask_path + ".tmp", "w") as f:
            json.dump(ask, f)
        os.replace(self.ask_path + ".tmp", self.ask_path)
        t0 = time.monotonic()
        answer = await asyncio.to_thread(self._wait_for_answer)
        built, forward = answer["seconds"]
        self.run.note(f"reference: weights built in {built:.1f}s beside the server's start, "
                      f"forward {forward:.1f}s; set-up waited {time.monotonic() - t0:.1f}s for it")
        tol = self.run.config["reference_tolerance"]["atol_over_scale"]
        ref = answer["logits"]
        scale = float(np.abs(ref).max())
        if served.shape != ref.shape:
            self.violations.append(f"logits of shape {served.shape}, the reference's {ref.shape}")
            return
        err = float(np.abs(served - ref).max())
        note = ""
        if "margins" in answer:
            m = answer["margins"]
            note = (f"; router margins: min {float(m.min()):.2g}, "
                    f"{int((m < 1e-3).sum())} of {m.size} under 1e-3")
        self.run.note(f"the float32 reference: max |diff| {err:.4g} at logit scale {scale:.4g} "
                      f"= {err / scale:.4f} of it (allowed {tol}){note}")
        if not err <= tol * scale:
            self.violations.append(
                f"logits are {err:.4g} from the float32 reference's (scale {scale:.4g}, "
                f"allowed {tol * scale:.4g})")
