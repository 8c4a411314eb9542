"""LocalModel: resolve a model reference into weights + config + card.

The CLI's model-acquisition path (reference: lib/llm/src/local_model.rs:27-80
`LocalModel::prepare` — resolve path or hf:// ref, build the MDC, attach).
Accepted references:

- ``preset:NAME`` — an architecture preset (models/config.py PRESETS) with
  seeded random weights and the hermetic ToyTokenizer; serves real traffic
  without checkpoint assets (the reference's echo-engine role, but through
  the full TPU engine).
- a local directory — HF checkout: ``config.json`` + ``*.safetensors`` +
  tokenizer files.
- ``hf://org/name`` — resolved through the local HF hub cache
  (``HF_HOME``, or the hub's default under the home directory); zero-egress environments must have
  the snapshot pre-cached (reference: lib/llm/src/hub.rs).
"""

from __future__ import annotations

import logging
import os
from dataclasses import dataclass
from pathlib import Path

from dynamo_tpu.llm.model_card import ModelDeploymentCard
from dynamo_tpu.models.config import PRESETS, ModelConfig

logger = logging.getLogger(__name__)


def _hub_cache_dirs() -> list[Path]:
    home = os.environ.get("HF_HOME")
    roots = [Path(home) / "hub"] if home else []
    roots.append(Path.home() / ".cache" / "huggingface" / "hub")
    return roots


def resolve_hub_snapshot(repo_id: str) -> str:
    """Find a cached hub snapshot for ``org/name`` (offline resolution —
    this environment has no egress; reference downloads live, hub.rs)."""
    folder = "models--" + repo_id.replace("/", "--")
    for root in _hub_cache_dirs():
        snaps = root / folder / "snapshots"
        if snaps.is_dir():
            revs = sorted(snaps.iterdir(), key=lambda p: p.stat().st_mtime)
            for rev in reversed(revs):
                if (rev / "config.json").exists():
                    return str(rev)
    raise FileNotFoundError(
        f"hf://{repo_id} not in the local hub cache "
        f"(searched {[str(r) for r in _hub_cache_dirs()]}); "
        "pre-download it or pass a local directory"
    )


@dataclass
class LocalModel:
    name: str
    config: ModelConfig
    model_path: str | None  # local dir with tokenizer/config, None = preset
    card: ModelDeploymentCard

    @staticmethod
    def prepare(
        ref: str,
        name: str | None = None,
        context_length: int | None = None,
        kv_block_size: int = 16,
    ) -> "LocalModel":
        model_path: str | None
        if ref.startswith("preset:"):
            preset = ref.split(":", 1)[1]
            if preset not in PRESETS:
                raise ValueError(
                    f"unknown preset {preset!r}; have {sorted(PRESETS)}"
                )
            config = PRESETS[preset]()
            model_path = None
            name = name or preset
        elif ref.endswith(".gguf"):
            from dynamo_tpu.llm.gguf import model_config_from_gguf, read_gguf

            if not Path(ref).exists():
                raise FileNotFoundError(ref)
            config = model_config_from_gguf(read_gguf(ref))
            model_path = ref  # load_tokenizer serves the embedded vocab
            name = name or Path(ref).stem
        else:
            if ref.startswith("hf://"):
                model_path = resolve_hub_snapshot(ref[len("hf://") :])
            else:
                model_path = ref
                if not (Path(model_path) / "config.json").exists():
                    raise FileNotFoundError(
                        f"{model_path} has no config.json (expected an HF "
                        "checkout, a .gguf file, 'preset:NAME', or "
                        "'hf://org/name')"
                    )
            config = ModelConfig.from_hf(model_path)
            name = name or Path(ref.rstrip("/")).name
        card = ModelDeploymentCard(
            name=name,
            model_path=model_path,  # None → ToyTokenizer (load_tokenizer)
            context_length=min(
                context_length or config.max_position, config.max_position
            ),
            kv_block_size=kv_block_size,
        )
        return LocalModel(
            name=name, config=config, model_path=model_path, card=card
        )

    def load_params(self, dtype="bfloat16"):
        """Load checkpoint weights ([in,out]-transposed), or None for presets
        (the engine runner seeds random params on device)."""
        if self.model_path is None:
            return None
        logger.info("loading weights from %s", self.model_path)
        if self.model_path.endswith(".gguf"):
            from dynamo_tpu.llm.gguf import load_gguf_weights, read_gguf

            return load_gguf_weights(
                self.config, read_gguf(self.model_path), dtype=dtype
            )
        from dynamo_tpu.models import llama

        return llama.load_hf_weights(self.config, self.model_path, dtype=dtype)
