"""Model registry — the scale-out families from BASELINE.json configs 2-4
(ResNet-18/50/101 for CIFAR-100/ImageNet, ViT stretch) register here as they
land. ``NetResDeep`` is special-cased in the trainer since its constructor
carries the tied-blocks flag."""

from __future__ import annotations

MODEL_REGISTRY: dict = {}

#: models whose module is imported when the model is first asked for
#: (``train/trainer.py::build_model``), not with the package: a run that
#: trains another model never pays for their imports
LAZY_MODELS = {"laguna_xs2": "tpu_ddp.models.decoder",
               "joyai_llm_flash": "tpu_ddp.models.decoder",
               "sdar_30b_a3b": "tpu_ddp.models.decoder",
               "nemotron3_super": "tpu_ddp.models.hybrid",
               "phi4_mini_flash": "tpu_ddp.models.sambay"}


def register(name: str):
    def deco(factory):
        MODEL_REGISTRY[name] = factory
        return factory

    return deco
