"""Model factory: name -> Flax module, mirroring the reference's
``create_model`` switch (``fedml_experiments/distributed/fedavg/
main_fedavg.py:217-252``) so reference run commands translate 1:1.
"""

from __future__ import annotations

import logging


def create_model(args, model_name, output_dim):
    """Return an uninitialized Flax module for ``model_name``.

    Accepted names (reference ``main_fedavg.py:217-252`` plus aliases):
    lr, cnn, cnn_dropout, resnet56, resnet110, resnet18_gn, resnet34_gn,
    resnet50_gn, mobilenet, mobilenet_v3, efficientnet[-b0..b7],
    vgg11/13/16/19, rnn (shakespeare LSTM), rnn_stackoverflow,
    transformer, moe_transformer, deepseek_v3 (a decoder read from
    ``--model_config``, a JSON file with the family's ``config.json``
    keys: latent attention, routed experts of which ``experts_held`` are
    here, ``models/deepseek_v3.py``).
    """
    from fedml_tpu import models

    logging.info("create_model. model_name = %s, output_dim = %s",
                 model_name, output_dim)
    group_norm = getattr(args, "group_norm_channels", 32) if args else 32
    only_digits = output_dim == 10
    # --model_dtype bf16: compute-dtype for the zoo (master params stay
    # fp32; convs/matmuls run 1-pass bf16 on the MXU -- the single biggest
    # single-chip throughput knob, see docs/PERFORMANCE.md)
    dt = {}
    dt_name = getattr(args, "model_dtype", None) if args else None
    if dt_name in ("bf16", "bfloat16"):
        import jax.numpy as jnp
        dt = {"dtype": jnp.bfloat16}

    if model_name == "lr":
        return models.LogisticRegression(num_classes=output_dim)
    if model_name == "cnn":
        return models.CNNOriginalFedAvg(only_digits=only_digits, **dt)
    if model_name == "cnn_dropout":
        return models.CNNDropOut(only_digits=only_digits, **dt)
    if model_name == "resnet56":
        return models.resnet56(class_num=output_dim, **dt)
    if model_name == "resnet110":
        return models.resnet110(class_num=output_dim, **dt)
    if model_name == "resnet18_gn":
        return models.resnet18_gn(class_num=output_dim, group_norm=group_norm,
                                  **dt)
    if model_name == "resnet34_gn":
        return models.resnet34_gn(class_num=output_dim, group_norm=group_norm,
                                  **dt)
    if model_name == "resnet50_gn":
        return models.resnet50_gn(class_num=output_dim, group_norm=group_norm,
                                  **dt)
    if model_name == "mobilenet":
        return models.MobileNet(num_classes=output_dim, **dt)
    if model_name == "mobilenet_v3":
        mode = getattr(args, "model_mode", "LARGE") if args else "LARGE"
        return models.MobileNetV3(model_mode=mode, num_classes=output_dim,
                                  **dt)
    if model_name.startswith("efficientnet"):
        name = "efficientnet-b0" if model_name == "efficientnet" else model_name
        return models.efficientnet(name, num_classes=output_dim, **dt)
    if model_name in ("vgg11", "vgg13", "vgg16", "vgg19"):
        fn = getattr(models, model_name)
        return fn(class_num=output_dim,
                  batch_norm=getattr(args, "vgg_bn", False) if args else False,
                  **dt)
    if model_name == "rnn":
        return models.RNNOriginalFedAvg(vocab_size=output_dim)
    if model_name == "rnn_fed_shakespeare":
        return models.RNNOriginalFedAvg(vocab_size=output_dim,
                                        output_all_timesteps=True)
    if model_name == "rnn_stackoverflow":
        return models.RNNStackOverflow(vocab_size=output_dim - 4)
    if model_name in ("transformer", "transformer_nwp"):
        return models.transformer_nwp(vocab_size=output_dim, **dt)
    if model_name == "moe_transformer":
        experts = getattr(args, "moe_experts", 8) if args else 8
        return models.MoETransformerLM(vocab_size=output_dim,
                                       n_experts=experts, **dt)
    if model_name == "deepseek_v3":
        path = getattr(args, "model_config", None) if args else None
        if not path:
            raise ValueError("--model deepseek_v3 is built from a "
                             "configuration file: pass --model_config")
        # the data's vocabulary is the model's (a sliced one is a smaller
        # vocabulary: ids, logits and loss are over it)
        return models.DeepseekV3LM(
            models.deepseek_v3.load_config(path, vocab_size=output_dim),
            **dt)
    raise ValueError(f"unknown model: {model_name}")
