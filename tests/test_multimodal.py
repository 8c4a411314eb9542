"""Multimodal serving tests: vision encoder, soft-prompt prefill vs the
no-cache oracle, and the full encode-worker → preprocessor → engine
pipeline (reference: examples/multimodal — encode_worker ahead of the
decode worker, README.md:18-30)."""

import base64
import io

import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.vision import VisionConfig

pytestmark = pytest.mark.anyio


def _npy_data_url(arr: np.ndarray) -> str:
    buf = io.BytesIO()
    np.save(buf, arr)
    return "data:application/x-npy;base64," + base64.b64encode(
        buf.getvalue()
    ).decode()


def _image(seed: float) -> np.ndarray:
    rng = np.random.default_rng(int(seed))
    return rng.random((32, 32, 3), np.float32)


def test_decode_image_npy_and_resize():
    from dynamo_tpu.llm.multimodal import decode_image

    img = _image(1)
    out = decode_image(_npy_data_url(img), 32)
    np.testing.assert_array_equal(out, img)

    # uint8 input normalizes; non-square resizes to the encoder's input.
    big = (np.arange(64 * 48 * 3) % 255).reshape(64, 48, 3).astype(np.uint8)
    out = decode_image(_npy_data_url(big), 32)
    assert out.shape == (32, 32, 3) and 0.0 <= out.min() and out.max() <= 1.0

    with pytest.raises(ValueError, match="data:"):
        decode_image("http://example.com/cat.png", 32)


def test_vision_encoder_shape_and_determinism():
    import jax

    from dynamo_tpu.models.vision import encode_image, init_vision_params

    cfg = VisionConfig.tiny_test(out_dim=64)
    params = init_vision_params(jax.random.PRNGKey(0), cfg)
    img = _image(2)
    a = np.asarray(encode_image(params, cfg, img))
    b = np.asarray(encode_image(params, cfg, img))
    assert a.shape == (cfg.num_patches, 64)
    np.testing.assert_array_equal(a, b)
    c = np.asarray(encode_image(params, cfg, _image(3)))
    assert np.abs(a - c).max() > 1e-3  # different image, different embeds


def test_runner_mm_prefill_matches_oracle():
    """A soft-prompt prefill span (unified_step(mm=...)) must agree with the no-cache oracle forward with
    the same embedding rows spliced in (greedy first token identical)."""
    import jax.numpy as jnp

    from dynamo_tpu.engine.runner import ModelRunner
    from dynamo_tpu.models import llama
    from stepdrive import step_token

    mcfg = ModelConfig.tiny_test()
    ecfg = EngineConfig(
        model=mcfg, num_blocks=32, max_num_seqs=2, max_model_len=128,
        dtype="float32",
    )
    runner = ModelRunner(ecfg, rng_seed=0)

    prompt = list(range(10, 40))  # 30 tokens
    rng = np.random.default_rng(0)
    seg = rng.standard_normal((8, mcfg.hidden_size)).astype(np.float32)
    off = 5  # embeds replace prompt positions 5..12

    tok = step_token(runner, prompt, [1, 2], mm=[(off, seg)])

    embeds = np.zeros((len(prompt), mcfg.hidden_size), np.float32)
    mask = np.zeros(len(prompt), bool)
    embeds[off : off + len(seg)] = seg
    mask[off : off + len(seg)] = True
    logits = llama.reference_forward(
        mcfg, runner.params, jnp.asarray(prompt, jnp.int32),
        embeds=jnp.asarray(embeds), embed_mask=jnp.asarray(mask),
    )
    assert tok == int(np.argmax(np.asarray(logits)[-1]))

    # And differs from the text-only prefill of the same tokens.
    runner2 = ModelRunner(ecfg, rng_seed=0)
    plain = step_token(runner2, prompt, [1, 2])
    assert plain == int(
        np.argmax(
            np.asarray(
                llama.reference_forward(
                    mcfg, runner2.params, jnp.asarray(prompt, jnp.int32)
                )
            )[-1]
        )
    )


async def test_multimodal_pipeline_end_to_end():
    """Chat request with an image content part: the preprocessor routes the
    image through the encode engine, placeholder tokens carry the patch
    embeddings into the TpuEngine, and greedy decoding is reproducible and
    image-dependent."""
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.llm.backend import Detokenizer
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.multimodal import (
        MultimodalPreprocessor,
        VisionEncodeEngine,
    )
    from dynamo_tpu.llm.protocols.openai import ChatCompletionRequest
    from dynamo_tpu.llm.tokenizer import ToyTokenizer
    from dynamo_tpu.runtime.engine import Context
    from dynamo_tpu.runtime.pipeline import Pipeline

    # Vocab pinned to the ToyTokenizer's single-byte ASCII range: ids
    # >= 256 decode to NOTHING and ids 128..255 are held as partial UTF-8
    # sequences, so random weights whose greedy continuation lands there
    # would make the text assertions below vacuously flaky. With 128 every
    # sampled token renders immediately as one character.
    import dataclasses

    mcfg = dataclasses.replace(ModelConfig.tiny_test(), vocab_size=128)
    ecfg = EngineConfig(
        model=mcfg, num_blocks=64, max_num_seqs=2, max_model_len=256,
        dtype="float32",
    )
    engine = TpuEngine(ecfg)
    await engine.start()
    vcfg = VisionConfig.tiny_test(out_dim=mcfg.hidden_size)
    encoder = VisionEncodeEngine(vcfg, rng_seed=7)
    card = ModelDeploymentCard(name="tiny-mm", model_path="toy")
    pipe = Pipeline.link(
        MultimodalPreprocessor(
            card,
            ToyTokenizer(),
            encoder,
            placeholder_token=1,
        ),
        Detokenizer(ToyTokenizer()),
        engine=engine,
    )

    def req(image_url):
        return ChatCompletionRequest(
            model="tiny-mm",
            messages=[
                {
                    "role": "user",
                    "content": [
                        {"type": "text", "text": "describe "},
                        {
                            "type": "image_url",
                            "image_url": {"url": image_url},
                        },
                    ],
                }
            ],
            stream=True,
            max_tokens=6,
            temperature=0.0,
            ext={"ignore_eos": True},
        )

    async def run(image_url):
        toks = []
        async for chunk in pipe.generate(Context(req(image_url))):
            for choice in getattr(chunk, "choices", []):
                if choice.delta and choice.delta.content:
                    toks.append(choice.delta.content)
        return "".join(toks)

    url_a = _npy_data_url(_image(11))
    out_a = await run(url_a)
    assert out_a  # produced text
    assert await run(url_a) == out_a  # greedy + same image => reproducible
    out_b = await run(_npy_data_url(_image(99)))
    assert out_b != out_a  # a different image changes the continuation

    # Text-only chats still flow through the same preprocessor untouched.
    plain = ChatCompletionRequest(
        model="tiny-mm",
        messages=[{"role": "user", "content": "hello"}],
        stream=True,
        max_tokens=4,
        temperature=0.0,
        ext={"ignore_eos": True},
    )
    got = []
    async for chunk in pipe.generate(Context(plain)):
        for choice in getattr(chunk, "choices", []):
            if choice.delta and choice.delta.content:
                got.append(choice.delta.content)
    assert got

    await engine.stop()


async def test_multimodal_model_discovery_deployment():
    """Full deployment shape: an encode worker and a TPU worker register
    over the runtime; the watcher builds the multimodal pipeline from the
    card (model_type=multimodal + extra.encode_endpoint) and requests flow
    across the request plane with embeddings on the wire."""
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.llm.discovery import (
        ModelManager,
        ModelWatcher,
        register_llm,
    )
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.multimodal import VisionEncodeEngine
    from dynamo_tpu.llm.protocols.openai import ChatCompletionRequest
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.engine import Context

    drt = await DistributedRuntime.in_process()
    mcfg = ModelConfig.tiny_test()
    vcfg = VisionConfig.tiny_test(out_dim=mcfg.hidden_size)

    enc_ep = drt.namespace("mm").component("encoder").endpoint("encode")
    await enc_ep.serve(VisionEncodeEngine(vcfg, rng_seed=7))

    engine = TpuEngine(
        EngineConfig(
            model=mcfg, num_blocks=64, max_num_seqs=2, max_model_len=256,
            dtype="float32",
        )
    )
    await engine.start()
    gen_ep = drt.namespace("mm").component("tpu").endpoint("generate")
    await gen_ep.serve(engine)
    card = ModelDeploymentCard(
        name="tiny-mm",
        model_path="toy",
        extra={
            "encode_endpoint": "mm.encoder.encode",
            "placeholder_token": 1,
        },
    )
    await register_llm(drt, gen_ep, card, model_type="multimodal")

    manager = ModelManager()
    await ModelWatcher(drt, manager).start()
    for _ in range(50):
        if manager.get("tiny-mm") is not None:
            break
        import asyncio

        await asyncio.sleep(0.05)
    pipe = manager.get("tiny-mm")
    assert pipe is not None

    body = {
        "model": "tiny-mm",
        "messages": [
            {
                "role": "user",
                "content": [
                    {"type": "text", "text": "look: "},
                    {
                        "type": "image_url",
                        "image_url": {"url": _npy_data_url(_image(42))},
                    },
                ],
            }
        ],
        "stream": True,
        "max_tokens": 4,
        "temperature": 0.0,
        "ext": {"ignore_eos": True},
    }
    chunks = []
    usage = None
    async for chunk in pipe.generate(
        Context(ChatCompletionRequest.model_validate(body))
    ):
        chunks.append(chunk)
        if getattr(chunk, "usage", None) is not None:
            usage = chunk.usage
    # The tiny model's greedy tokens may fall outside the byte-level
    # tokenizer's printable range, so assert on the stream itself: deltas
    # arrived and the final usage counts the generated tokens.
    assert chunks
    assert usage is not None and usage.completion_tokens == 4

    await engine.stop()
    await drt.shutdown()
