"""Production inference tier: continuous-batching multi-tenant serving
on the AOT path (ISSUE 9).

The training side of this repo got its perf PRs (2, 4, 5, 7); this
package is the serving half of the north star — the role the
reference's C++ NativePredictor + pre-compiled-subgraph engine cache
played (`/root/reference/paddle/fluid/inference/`), rebuilt TPU-native
on the primitives already here:

- `inference/aot.py` zero-retrace executables -> per-bucket compiled
  engines (engine.py);
- the PR 2 Scope/prepared device-resident parameter staging -> each
  tenant's weights live on device across requests;
- the PR 4 fastwire framing -> the socket request plane (wire.py);
- PR 6 metrics/spans -> queue-wait / batch-assembly / dispatch phases
  in trace_report.py and always-on QPS/latency/occupancy metrics.

Shapes: Orca-style continuous batching (Yu et al., OSDI '22) under a
Clipper-style launch deadline (Crankshaw et al., NSDI '17) — see
batcher.py.  Load harness: tools/serve_bench.py -> SERVE_BENCH.json.
"""
from __future__ import annotations

from .batcher import set_metrics_enabled
from .engine import ModelEngine, bucket_ladder
from .fleet import (FleetEndpoint, FleetWorker, LocalTransport,
                    SocketTransport)
from .generative import GenerativeEngine
from .lm import LMConfig, tiny_lm
from .kv_cache import BlockPool
from .router import FleetRouter, default_fleet_slos
from .server import InferenceServer
from .wire import PredictClient, RemoteError

__all__ = ["BlockPool", "FleetEndpoint", "FleetRouter", "FleetWorker",
           "GenerativeEngine", "InferenceServer", "LMConfig",
           "LocalTransport", "ModelEngine", "PredictClient",
           "RemoteError", "SocketTransport", "bucket_ladder",
           "create_c_server", "default_fleet_slos",
           "set_metrics_enabled", "tiny_lm"]


class _CServerHandle:
    """What the C API holds: predictor-shaped ``run(feed)`` (returns
    objects with ``.data``, like inference.PaddlePredictor.run) routed
    through an InferenceServer's in-process submit/future plane, so a
    C program gets the continuous batcher, not a private executor."""

    def __init__(self, server, model_name):
        self.server = server
        self.model_name = model_name

    def run(self, feed):
        from paddle_tpu.inference import PaddleTensor

        outs = self.server.predict(self.model_name, feed)
        return [PaddleTensor(name=k, data=v) for k, v in outs.items()]

    Run = run

    def close(self):
        self.server.close()


def create_c_server(model_dir, use_accelerator=0, model_name="default"):
    """Entry point for capi.cc's pd_create_server: one in-process
    InferenceServer hosting ``model_dir`` as tenant ``model_name``,
    wrapped predictor-shaped for the shared C marshalling."""
    import paddle_tpu.fluid as fluid

    place = fluid.TPUPlace() if use_accelerator else fluid.CPUPlace()
    server = InferenceServer(place=place)
    server.load(model_name, model_dir)
    return _CServerHandle(server, model_name)
