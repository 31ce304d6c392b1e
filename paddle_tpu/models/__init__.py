"""Model zoo — the reference's benchmark/book models rebuilt TPU-first
(reference: benchmark/fluid/models/, tests/book/)."""

from . import (alexnet, bert, deepfm, googlenet, gpt, hybrid, mnist,
               recommender, resnet, se_resnext, speculative,
               stacked_lstm, transformer, vgg, vit)

__all__ = ["alexnet", "bert", "deepfm", "googlenet", "gpt", "hybrid",
           "mnist",
           "recommender", "resnet", "se_resnext", "speculative",
           "stacked_lstm", "transformer", "vgg", "vit"]
