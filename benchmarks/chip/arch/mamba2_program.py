"""The program's own objects for a Mamba-2 configuration file: its
``ModelConfig``, ``TrainConfig`` and ``LoopConfig``."""
from __future__ import annotations

import jax.numpy as jnp

from repro.models import ModelConfig
from repro.optim import AdamWConfig
from repro.train.loop import LoopConfig
from repro.train.steps import TrainConfig

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def model_config(c: dict) -> ModelConfig:
    return ModelConfig(
        name=c["name"], family="ssm", num_layers=c["n_layer"],
        d_model=c["d_model"], num_heads=c["d_model"] // c["headdim"],
        num_kv_heads=c["d_model"] // c["headdim"], d_ff=0,
        vocab_size=c["vocab_size"], block_pattern=("ssd",),
        ssm_state=c["d_state"], ssm_head_dim=c["headdim"],
        ssm_expand=c["expand"], ssm_groups=c["ngroups"],
        ssm_conv=c["d_conv"], ssm_chunk=c["chunk_size"],
        norm_eps=c["norm_eps"], tie_embeddings=c["tie_embeddings"])


def train_config(c: dict) -> TrainConfig:
    o = c["optimizer"]
    return TrainConfig(
        dtype=DTYPES[c["precision"]["compute"]], z_loss=o["z_loss"],
        peak_lr=o["peak_lr"],
        adamw=AdamWConfig(b1=o["b1"], b2=o["b2"], eps=o["eps"],
                          weight_decay=o["weight_decay"],
                          clip_norm=o["clip_norm"]))


def loop_config(c: dict, traffic: dict, seed: int) -> LoopConfig:
    o = c["optimizer"]
    return LoopConfig(total_steps=o["total_steps"],
                      ckpt_every=traffic["save_every"] or o["total_steps"],
                      log_every=traffic["log_every"], warmup=o["warmup"],
                      seed=seed % 2 ** 31, keep_ckpts=traffic.get("keep", 3))
