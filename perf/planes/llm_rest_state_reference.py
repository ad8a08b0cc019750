"""planes/llm_rest_reference.py for a model whose layers keep a float32 matrix
state a sequence (a mamba layer's h), with a second comparison: the STATE
itself, read back from the server's cache, against the plain reference's.

Why: the logits of a 40-layer model served in bf16 activations lie 3-5 % of
their scale from the float32 reference's, and a state held in bf16 moves them
by about as much again, in no direction a limit on logits can tell from that
noise (PERF.md section 4, PR 53).  The state of the FIRST such layer has no
such noise before it: its inputs are the table's rows and one projection.  So
before the window one more seeded request, the cell's `state_probe` (a prompt
that crosses a chunk boundary, then a few hundred decode steps, so that h goes
through the chunked form, a padded chunk and the step's kernel), is sent with
"state": true, and the reply's state (`tokens` tokens of prompt + reply fed,
transport/rest.py) is compared with the reference's scan over the same tokens,
a head at a time, over the heads that CARRY their state longest:

    max over heads of memory >= `carried_tokens` of
        |h_served - h_reference|_F / |h_reference|_F

A head's memory is 1 / (|A| softplus(dt_bias)) tokens, from the layer's own
leaf (the reference's answer has it).  Why those heads: every head's h is off
by what its bf16 INPUTS put there (x and B rounded once: 0.1-0.6 % of a head's
size, the most where h is the last token or two), while a rounding of h itself
after every token adds up over as many tokens as the head remembers: over a
hundred of them it is several times the inputs' share, under ten it is lost in
it.  `correct` is false if the statistic is over `reference_tolerance.
state_rtol`.  Every run also prints what the SAME reference reads when it
rounds h to bf16 after every token (`lax.reduce_precision`), which is what a
cache that held h in bf16 would do: the reading the limit has to lie under,
beside the one it has to lie over.
"""

from __future__ import annotations

import asyncio
import base64
import json
import os
import time

import numpy as np

from planes import llm_rest, llm_rest_reference


def by_head(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """``got`` and ``want`` [H, ...]: a head's distance between them as a share
    of the reference head's own size, [H]."""
    heads = len(want)
    return (np.linalg.norm((got - want).reshape(heads, -1), axis=1)
            / np.linalg.norm(want.reshape(heads, -1), axis=1))


class Plane(llm_rest_reference.Plane):
    def prepare(self) -> None:
        super().prepare()
        self.state_ask_path = os.path.join(self.run.out_dir, "reference_state_ask.json")
        self.state_answer_path = os.path.join(self.run.out_dir, "reference_state_answer.npz")

    async def probe(self, phase: str, rng: np.random.Generator) -> None:
        await super().probe(phase, rng)
        if phase == "before":
            await self._state_against_reference(rng)

    def _wait_for_state(self) -> dict:
        while not os.path.exists(self.state_answer_path):
            if self.reference.poll() is not None:
                raise RuntimeError("the float32 reference failed; see reference.log")
            time.sleep(0.1)
        with np.load(self.state_answer_path) as answer:
            return {k: answer[k] for k in answer.files}

    async def _state_against_reference(self, rng: np.random.Generator) -> None:
        spec = self.run.cell["state_probe"]
        prompt = llm_rest.letters(rng, spec["prompt_tokens"])
        body = {"prompt": prompt, "max_new_tokens": spec["output_tokens"],
                "seed": llm_rest.PROBE_SEED, "state": True}
        t0 = time.monotonic()
        async with self.session.post(self.url + "/v1/generate",
                                     data=json.dumps(body).encode()) as resp:
            if resp.status != 200:
                raise RuntimeError(f"state probe failed: HTTP {resp.status} "
                                   f"{(await resp.text())[:300]}")
            reply = await resp.json(content_type=None)
        asked = time.monotonic() - t0
        packed = reply["state"]
        known = [ord(c) for c in prompt] + reply["tokens"]
        if packed["tokens"] > len(known):
            self.violations.append(
                f"the state had been fed {packed['tokens']} tokens, the reply holds {len(known)}")
            return
        # the server holds a head's h transposed, [H, d_state, d_head]
        served = np.swapaxes(np.frombuffer(base64.b64decode(packed["base64"]),
                                           "<f4").reshape(packed["shape"]), 1, 2)
        with open(self.state_ask_path + ".tmp", "w") as f:
            json.dump({"tokens": known[:packed["tokens"]], "layer": packed["layer"]}, f)
        os.replace(self.state_ask_path + ".tmp", self.state_ask_path)
        t0 = time.monotonic()
        answer = await asyncio.to_thread(self._wait_for_state)
        tol = self.run.config["reference_tolerance"]["state_rtol"]
        if served.shape != answer["state"].shape:
            self.violations.append(
                f"state of shape {served.shape}, the reference's {answer['state'].shape}")
            return
        heads = {"float32": by_head(served, answer["state"]),
                 "h_held_in_bf16": by_head(served, answer["state_bf16"])}
        with open(os.path.join(self.run.out_dir, "state_probe.json"), "w") as f:
            json.dump({"layer": packed["layer"], "tokens": packed["tokens"],
                       "memory": answer["memory"].tolist(),
                       "off_by_head": {k: v.tolist() for k, v in heads.items()}}, f)
        carries = answer["memory"] >= spec["carried_tokens"]
        if not carries.any():
            self.violations.append(
                f"no head of layer {packed['layer']} carries its state "
                f"{spec['carried_tokens']} tokens: nothing to judge its precision by")
            return
        off, held_low = (float(v[carries].max()) for v in heads.values())
        self.run.note(
            f"the float32 reference's state: layer {packed['layer']} after "
            f"{packed['tokens']} tokens ({spec['prompt_tokens']} prefilled, "
            f"{packed['tokens'] - spec['prompt_tokens']} stepped; asked in {asked:.1f}s, "
            f"the reference's two scans {float(answer['seconds']):.1f}s, set-up waited "
            f"{time.monotonic() - t0:.1f}s): of the {int(carries.sum())} heads that carry "
            f"{spec['carried_tokens']} tokens or more the furthest is {off:.5f} of its own "
            f"size (allowed {tol}; all {len(carries)} heads: {float(heads['float32'].max()):.5f}); "
            f"from the reference that holds h in bf16 {held_low:.5f} = {held_low / tol:.2f} x "
            f"the limit")
        if not off <= tol:
            self.violations.append(
                f"layer {packed['layer']}'s state is {off:.4g} of a head's size from the "
                f"float32 reference's (allowed {tol})")
