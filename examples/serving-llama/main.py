"""The TPU-native flagship: continuous-batching LLM serving with paged
KV and optional speculative decoding, behind /generate
(JSON + SSE streaming) and /v1/models.

Environment knobs (all optional): TPU_KV_PAGE_SIZE=16,
TPU_SPEC_TOKENS=6, TPU_BATCH_MAX_SLOTS, ... (serving/engine.py
EngineConfig.from_config). Swap init_params for
ServingEngine.from_hf("/path/to/llama") to serve real weights."""

import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import gofr_tpu
from gofr_tpu.models import llama
from gofr_tpu.serving import (
    ByteTokenizer,
    DeviceTelemetry,
    EngineConfig,
    ServingEngine,
)
from gofr_tpu.serving.handlers import register_generation_routes


def build_app(config=None) -> gofr_tpu.App:
    app = gofr_tpu.App(config)
    cfg = llama.LlamaConfig(
        vocab_size=512, d_model=128, n_layers=4, n_heads=8, n_kv_heads=4,
        d_ff=256, max_seq_len=512,
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    engine = ServingEngine(
        cfg, params,
        EngineConfig.from_config(app.container.config),
        ByteTokenizer(cfg.vocab_size),
        metrics=app.container.metrics_manager,
        logger=app.container.logger,
        tracer=app.container.tracer,
    )
    register_generation_routes(app, engine)  # + /v1/models + /requestz
    # HBM + duty-cycle gauges, health embed, heartbeat headroom
    # (docs/observability.md "TPU device telemetry")
    telemetry = DeviceTelemetry(
        engine, metrics=app.container.metrics_manager,
        logger=app.container.logger,
    )
    app.on_start(lambda ctx: telemetry.start())
    app.on_shutdown(telemetry.stop)
    return app


if __name__ == "__main__":
    build_app().run()
