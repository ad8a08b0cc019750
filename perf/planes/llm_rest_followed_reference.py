"""planes/llm_rest_reference.py for a model whose router's near-ties decide
the logits: the probe's reply also carries "routing", the experts every
processed token took in every MoE layer (out of the same step programs), and
the configuration's plain reference FOLLOWS them: it takes the served experts,
weighs them by its own float32 scores, and computes everything else itself.

Why: the comparison is of one function of the weights computed in two
arithmetics.  Where a token's last chosen and first unchosen expert score
within the served arithmetic's noise of each other, which of the two is taken
is not a property of the program; and where the chosen experts carry half of
the block's output each, as a renormalised top-4 does, one such choice moves
the token's state by a tenth, every later choice of that token and of every
token that attends to it sees another input, and the two sides stop computing
the same function.  Followed, the logits agree to the activations' rounding,
and every part of the model is visible in them again.  What following would
hide, a served path that chooses by ANOTHER RULE, is the second limit:
`choice_behind`, by how much the reference's own last choice may beat the
worst expert it was made to follow (its score plus selection bias, at the
reference's own input: 0 where both sides chose alike, the served noise at a
near-tie, the whole spread of the scores under another rule).

`correct` is false if the logits are further than `atol_over_scale` of their
scale from the reference's, or any choice is further than `choice_behind`
behind, or the reply has no routing.
"""

from __future__ import annotations

import asyncio
import base64
import json
import os
import time

import numpy as np

from planes import llm_rest, llm_rest_reference


def unpack(packed: dict, dtype: str) -> np.ndarray:
    return np.frombuffer(base64.b64decode(packed["base64"]), dtype).reshape(packed["shape"])


class Plane(llm_rest_reference.Plane):
    async def _served(self) -> tuple:
        """(logits [rows, vocab], routing [tokens, moe layers, k] or None, its first token)"""
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
        routing = reply.get("routing")
        if routing is None:
            return unpack(reply["logits"], "<f4"), None, 0
        return unpack(reply["logits"], "<f4"), unpack(routing, "<i4"), int(routing["first_token"])

    async def _against_reference(self) -> None:
        served, routing, first_token = await self._served()
        prompt = [ord(c) for c in self._probe_prompt]   # the byte tokenizer's ids
        ask = {"tokens": prompt + self._probe,
               "rows": [len(prompt) - 1, len(prompt) - 1 + len(self._probe)]}
        if routing is None or first_token != 0:
            self.violations.append("the probe's reply has no routing from its first token on: "
                                   "the reference cannot follow the served choices")
        else:
            ask["follow"] = routing.tolist()
        with open(self.ask_path + ".tmp", "w") as f:
            json.dump(ask, f)
        os.replace(self.ask_path + ".tmp", self.ask_path)
        t0 = time.monotonic()
        answer = await asyncio.to_thread(self._wait_for_answer)
        built, forward = answer["seconds"]
        self.run.note(f"reference: weights built in {built:.1f}s beside the server's start, "
                      f"forward {forward:.1f}s; set-up waited {time.monotonic() - t0:.1f}s for it")
        limits = self.run.config["reference_tolerance"]
        tol, behind_tol = limits["atol_over_scale"], limits["choice_behind"]
        ref = answer["logits"]
        scale = float(np.abs(ref).max())
        if served.shape != ref.shape:
            self.violations.append(f"logits of shape {served.shape}, the reference's {ref.shape}")
            return
        err = float(np.abs(served - ref).max())
        m, behind = answer["margins"], answer["behind"]
        self.run.note(
            f"the float32 reference, following the served experts: max |diff| {err:.4g} at logit "
            f"scale {scale:.4g} = {err / scale:.4f} of it (allowed {tol}); {int((behind > 0).sum())} "
            f"of {behind.size} choices fell the other way, the furthest {float(behind.max()):.4g} "
            f"behind (allowed {behind_tol}); router margins: min {float(m.min()):.2g}, "
            f"{int((m < 1e-3).sum())} of {m.size} under 1e-3")
        if not err <= tol * scale:
            self.violations.append(
                f"logits are {err:.4g} from the float32 reference's (scale {scale:.4g}, "
                f"allowed {tol * scale:.4g})")
        if not float(behind.max()) <= behind_tol:
            self.violations.append(
                f"a served choice is {float(behind.max()):.4g} behind the reference's own "
                f"(allowed {behind_tol}): the served router chooses by another rule")
