"""In-process consumption of the NATIVELY-BUILT train step.

`FLAGS_native_build=1` routes `Executor.run` through here: the block's
XLA computation is built by the C++ kernel registry
(native/xla_train/xla_train.cc — the reference's REGISTER_OPERATOR
analogue, reference framework/op_registry.h:197-270), dumped as an
HloModuleProto (`xla_train --hlo`), converted to StableHLO, and
compiled/executed by the SAME jax runtime the traced path uses. The
Python trace path remains the numerical oracle
(tests/test_native_executor.py asserts per-step loss parity to 1e-5).
"""
from __future__ import annotations

import json
import os
import subprocess
import tempfile
from typing import Dict, List

import jax
import numpy as np

__all__ = ["NativeBuiltStep"]


class NativeBuiltStep:
    """One compiled train step whose XLA program was built in C++."""

    def __init__(self, program, scope, feed_arrays: Dict,
                 fetch_names: List[str]):
        from ..inference.export import export_train_program
        from . import build_xla_train

        self.fetch_names = list(fetch_names)
        # the artifact (which snapshots EVERY parameter to data/*.bin)
        # is only needed while the subprocess builds the HLO — delete
        # it as soon as the computation and manifest are in memory
        with tempfile.TemporaryDirectory(
                prefix="ptp_native_build_") as tmp:
            art = os.path.join(tmp, "art")
            export_train_program(
                program, scope,
                {n: np.asarray(v) for n, v in feed_arrays.items()},
                fetch_names, art)
            binary = build_xla_train()
            hlo_path = os.path.join(art, "step.hlo.pb")
            proc = subprocess.run([binary, art, "--hlo", hlo_path],
                                  capture_output=True, text=True,
                                  timeout=600)
            if proc.returncode != 0:
                raise RuntimeError(
                    f"FLAGS_native_build: the C++ builder rejected "
                    f"the block (exit {proc.returncode}): "
                    f"{proc.stderr.strip()[-2000:]}")
            with open(hlo_path, "rb") as f:
                hlo = f.read()
            with open(os.path.join(art, "manifest.json")) as f:
                self._manifest = json.load(f)
        from jax._src.lib import xla_client

        stablehlo = xla_client._xla.mlir.hlo_to_stablehlo(hlo)
        backend = jax.devices()[0].client
        self._loaded = backend.compile_and_load(
            stablehlo, backend.devices()[:1],
            xla_client.CompileOptions())
        self.state_out_names = [
            s["name"] for s in self._manifest["outputs"]
            if s["kind"] == "state"]

    def run(self, scope, feed_arrays: Dict):
        """Execute one step: state from the scope, feeds from the
        caller; state outputs thread back into the scope. Returns
        {fetch_name: array}."""
        args = []
        for spec in self._manifest["inputs"]:
            if spec["kind"] == "feed":
                v = feed_arrays[spec["name"]]
            else:
                v = scope._get(spec["name"])
                if v is None:
                    raise RuntimeError(
                        f"Variable {spec['name']!r} is used before "
                        f"initialization -- run the startup program "
                        f"first")
            want = spec["dtype"]
            if not isinstance(v, jax.Array) or str(v.dtype) != want:
                v = jax.device_put(np.ascontiguousarray(
                    np.asarray(v).astype(want)))
            args.append(v)
        outs = self._loaded.execute(args)
        fetches = {}
        for spec, val in zip(self._manifest["outputs"], outs):
            if spec["kind"] == "fetch":
                fetches[spec["name"]] = val
            elif spec.get("feeds_input", -1) >= 0:
                scope._set(spec["name"], val)
        return fetches
