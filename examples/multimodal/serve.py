"""Multimodal deployment: encode worker + TPU decode worker + OpenAI HTTP.

The reference's multimodal example shape (reference: examples/multimodal
README.md:18-30 — an encode_worker runs the vision encoder ahead of the
decode worker; the processor routes image content through it). Here both
workers join one in-process runtime; images ride OpenAI `image_url`
content parts as data: URLs, the vision encoder turns them into
soft-prompt embeddings, and the engine splices them into prefill in
place of placeholder tokens.

Run (CPU works):
  JAX_PLATFORMS=cpu python examples/multimodal/serve.py

Then query:
  python examples/multimodal/client.py http://127.0.0.1:8080
"""

import asyncio
import os
import sys

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..")
)

from dynamo_tpu.engine.config import EngineConfig  # noqa: E402
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.llm.discovery import ModelManager, ModelWatcher, register_llm
from dynamo_tpu.llm.http_service import HttpService
from dynamo_tpu.llm.model_card import ModelDeploymentCard
from dynamo_tpu.llm.multimodal import VisionEncodeEngine
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.vision import VisionConfig
from dynamo_tpu.runtime.distributed import DistributedRuntime


async def main() -> None:
    mcfg = ModelConfig.tiny_test()
    vcfg = VisionConfig.tiny_test(out_dim=mcfg.hidden_size)

    # Both model builds happen BEFORE the runtime exists: device dispatch /
    # XLA compile on the event loop would starve the lease keepalive past
    # its TTL and deregister everything (10s TTL; device init plus the
    # first compiles take longer than that).
    engine = TpuEngine(
        EngineConfig(
            model=mcfg, num_blocks=256, max_num_seqs=4, max_model_len=512,
            multimodal=True,
        )
    )
    await engine.start()
    encoder = await asyncio.to_thread(VisionEncodeEngine, vcfg)

    drt = await DistributedRuntime.in_process()
    # Encode worker (scales independently of decode workers in a real
    # deployment — here same process for a one-file example).
    await drt.namespace("mm").component("encoder").endpoint("encode").serve(
        encoder
    )
    gen_ep = drt.namespace("mm").component("tpu").endpoint("generate")
    await gen_ep.serve(engine)
    await register_llm(
        drt,
        gen_ep,
        ModelDeploymentCard(
            name="tiny-mm",
            model_path="toy",
            extra={
                "encode_endpoint": "mm.encoder.encode",
                "placeholder_token": 1,
            },
        ),
        model_type="multimodal",
    )

    manager = ModelManager()
    await ModelWatcher(drt, manager).start()
    while not manager.models():
        await asyncio.sleep(0.05)
    service = HttpService(manager, host="127.0.0.1", port=8080)
    await service.start()
    print(f"multimodal OpenAI server on http://127.0.0.1:{service.port}")
    try:
        await asyncio.Event().wait()
    finally:
        await service.stop()
        await engine.stop()
        await drt.shutdown()


if __name__ == "__main__":
    asyncio.run(main())
