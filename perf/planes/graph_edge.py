"""Graph plane: a JAX_SERVER leaf behind `cli edge` on the DEVICE_MODEL plane
(native edge -> ring kind 2 -> ModelExecutor -> bucketed jit), driven over gRPC
`Seldon/Predict` with `data.tensor` (packed doubles, the Seldon v0.4 tensor
contract).  `cli edge` builds native/ itself with make on start (incremental
after a checkout's first run).
"""

from __future__ import annotations

import asyncio
import json
import os
import shutil
import subprocess
import sys

import aiohttp
import grpc
import numpy as np

from planes import wire
from server import child_env

HERE = os.path.dirname(os.path.abspath(__file__))
GRPC_OPTIONS = [("grpc.max_send_message_length", -1),
                ("grpc.max_receive_message_length", -1)]


class Plane:
    def __init__(self, run):
        self.run = run
        self.port, self.grpc_port = run.free_port(), run.free_port()
        self.server_cfg = run.config["server"]
        self.shape = list(self.server_cfg["input_shape"])
        self.classes = int(run.config["num_classes"])
        self.violations: list = []
        self.pool: dict = {}
        self.channel = self.session = self.reference = None

    # -- launch ----------------------------------------------------------
    def _helper(self, script: str, *args: str) -> subprocess.Popen:
        return subprocess.Popen(
            [sys.executable, script, *args], cwd=self.run.repo,
            env=child_env("cpu", False, {}), stdout=subprocess.DEVNULL,
            stderr=open(os.path.join(self.run.out_dir, "helper.log"), "ab"))

    def prepare(self) -> None:
        """Seeded checkpoint (helper child on the CPU), the graph's spec, the
        probe image, and the plain float32 reference started beside the
        server's own start-up; its answer is collected after the window."""
        out = self.run.out_dir
        self.ckpt = os.path.join(out, "ckpt")
        shutil.rmtree(self.ckpt, ignore_errors=True)
        export = self._helper(os.path.join(HERE, "export_checkpoint.py"), self.ckpt,
                              str(self.run.seed), json.dumps(self.server_cfg))
        if export.wait() != 0:
            raise RuntimeError("checkpoint export failed; see helper.log")
        self.spec_path = os.path.join(out, "graph.json")
        with open(self.spec_path, "w") as f:
            json.dump({"name": "perf", "graph": {
                "name": self.server_cfg["model"], "type": "MODEL",
                "implementation": "JAX_SERVER", "modelUri": self.ckpt}}, f)
        self.probe_image = self.run.rngs["probe"].random((1, *self.shape)).round(3)
        np.save(os.path.join(out, "probe.npy"), self.probe_image)
        self.reference = self._helper(
            os.path.join(self.run.perf_dir, self.run.config["reference"]),
            self.ckpt, os.path.join(out, "probe.npy"), os.path.join(out, "ref.npy"))

    def command(self) -> dict:
        return {
            "argv": ["edge", "--spec", self.spec_path, "--port", str(self.port),
                     "--grpc-port", str(self.grpc_port), "--workers", "1"],
            "cwd": self.run.repo, "env": {},
            "ready": f"http://127.0.0.1:{self.port}/ready",
        }

    async def connect(self) -> None:
        self.channel = grpc.aio.insecure_channel(
            f"127.0.0.1:{self.grpc_port}", options=GRPC_OPTIONS)
        self.predict = self.channel.unary_unary("/seldon.protos.Seldon/Predict")
        self.session = aiohttp.ClientSession()

    async def close(self) -> None:
        if self.channel is not None:
            await self.channel.close()
        if self.session is not None:
            await self.session.close()
        if self.reference is not None and self.reference.poll() is None:
            self.reference.kill()
            self.reference.wait()
        shutil.rmtree(getattr(self, "ckpt", ""), ignore_errors=True)  # ~100 MB

    # -- requests --------------------------------------------------------
    def make_request(self, sizes: dict, rng: np.random.Generator) -> dict:
        """One of `payload_pool` distinct pre-encoded requests of sizes['rows']
        rows: a row is 1.2 MB of doubles, so thousands of distinct ones would
        not fit, and encoding inside the window would starve the generator."""
        rows = sizes["rows"]
        pick = int(rng.integers(self.run.cell["traffic"]["payload_pool"]))
        key = (rows, pick)
        if key not in self.pool:
            images = rng.random((rows, *self.shape)).round(3)
            self.pool[key] = wire.encode_tensor_message(images)
        return {"body": self.pool[key], "rows": rows}

    def work(self, sizes: dict) -> float:
        return float(sizes["rows"])

    def samples(self, rec: dict) -> dict:
        return {"latency_s": rec["done"] - rec["due"],
                "latency_from_send_s": rec["done"] - rec["sent"]}

    async def _call(self, body: bytes, rows: int):
        reply = wire.decode_tensor_message(await self.predict(body, timeout=120.0))
        if reply.shape != (rows, self.classes) or not np.isfinite(reply).all():
            self.violations.append(f"reply of shape {reply.shape} or not finite, "
                                   f"wanted ({rows}, {self.classes})")
        return reply

    async def send(self, request: dict) -> dict:
        try:
            await self._call(request["body"], request["rows"])
        except (grpc.RpcError, ValueError) as e:
            code = e.code().name if isinstance(e, grpc.RpcError) else "reply"
            return {"ok": False, "error": f"{code}: {e}"[:200]}
        return {"ok": True}

    # -- correctness, outside the window -----------------------------------
    async def probe(self, phase: str, rng: np.random.Generator) -> None:
        """before: the fixed image alone, and as row 0 of a request of the
        largest bucket, which must agree within what bf16 allows (the other
        bucket is another compile of the same bf16 arithmetic).  after: the
        fixed image again, bit-equal; then the float32 reference's answer."""
        single = wire.encode_tensor_message(self.probe_image)
        if phase == "before":
            self.logits = await self._call(single, 1)
            big = max(self.server_cfg["batch_buckets"])
            batch = np.concatenate(
                [self.probe_image, rng.random((big - 1, *self.shape)).round(3)])
            many = await self._call(wire.encode_tensor_message(batch), big)
            self._agree("row 0 of the largest bucket", many[:1], self.logits)
            return
        again = await self._call(single, 1)
        if not (again == self.logits).all():
            self.violations.append("the fixed image gave other logits after the window")
        if await asyncio.to_thread(self.reference.wait) != 0:
            raise RuntimeError("the float32 reference failed; see helper.log")
        ref = np.load(os.path.join(self.run.out_dir, "ref.npy"))
        self._agree("the float32 reference", ref, self.logits)

    def _agree(self, what: str, a: np.ndarray, b: np.ndarray) -> None:
        tol = self.run.config["reference_tolerance"]["atol_over_scale"]
        scale = float(np.abs(b).max())
        err = float(np.abs(a - b).max())
        self.run.note(f"{what}: max |diff| {err:.4g} at logit scale {scale:.4g} "
                      f"(allowed {tol * scale:.4g})")
        if not err <= tol * scale:
            self.violations.append(f"{what} is {err:.4g} from the single-row reply "
                                   f"(logit scale {scale:.4g}, allowed {tol * scale:.4g})")

    # -- what readers read -------------------------------------------------
    async def scrape(self) -> dict:
        async with self.session.get(f"http://127.0.0.1:{self.port}/metrics") as resp:
            return {"metrics": await resp.text()}
