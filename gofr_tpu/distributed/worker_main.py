"""Standalone cluster worker process for multi-host tests and local
pod simulation:

    python -m gofr_tpu.distributed.worker_main \
        --leader 127.0.0.1:9400 --port 9411 --host-id w1

Boots a tiny-llama ServingEngine behind the gRPC Inference service,
registers with the leader, and heartbeats until killed — one OS process
per "host", which is exactly how the driver-facing multi-host story
runs on CPU (tests/test_multihost.py kills one of these and watches the
leader fail over).
"""

from __future__ import annotations

import asyncio
import signal
import sys


def _parse_args(argv: list[str]) -> dict[str, str]:
    out: dict[str, str] = {}
    i = 0
    while i < len(argv):
        if argv[i].startswith("--"):
            out[argv[i][2:].replace("-", "_")] = argv[i + 1]
            i += 2
        else:
            i += 1
    return out


def main(argv: list[str] | None = None) -> int:
    args = _parse_args(argv if argv is not None else sys.argv[1:])
    leader = args["leader"]
    port = int(args["port"])
    host_id = args.get("host_id", f"worker-{port}")

    # the platform is the launcher's choice (JAX_PLATFORMS): a worker
    # started without one needs a TPU, and ServingEngine refuses anything
    # else (ops/backend.require_requested_backend)
    import jax

    from gofr_tpu.config import MapConfig
    from gofr_tpu.distributed import WorkerAgent
    from gofr_tpu.grpcx import GRPCServer, InferenceService
    from gofr_tpu.models import llama
    from gofr_tpu.serving import ByteTokenizer, EngineConfig, ServingEngine
    from gofr_tpu.testutil import new_mock_container

    cfg = llama.LlamaConfig.tiny(vocab_size=300)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    engine = ServingEngine(
        cfg, params,
        EngineConfig(max_slots=2, max_seq_len=64, prefill_buckets=(16, 32)),
        ByteTokenizer(),
    )
    engine.start()

    container, _ = new_mock_container()
    server = GRPCServer(container, port, MapConfig({}, use_env=False))
    server.register(InferenceService(engine))

    async def run() -> None:
        await server.start()
        agent = WorkerAgent(
            leader, host_id, f"127.0.0.1:{port}",
            n_devices=jax.local_device_count(),
            health_fn=container.health,
            logger=container.logger,
        )
        await agent.start()
        print(f"WORKER_READY {host_id} {port}", flush=True)
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, stop.set)
        await stop.wait()
        await agent.stop()
        await server.shutdown(grace=0.2)

    asyncio.run(run())
    engine.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
