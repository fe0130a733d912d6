"""On-device augmentation (crop/flip/Cutout) -- reference
``fedml_api/data_preprocessing/cifar10/data_loader.py:57-76``."""

import pytest
import types

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu import models
from fedml_tpu.algorithms.fedavg import FedAvgAPI
from fedml_tpu.algorithms.specs import make_classification_spec
from fedml_tpu.data.augment import make_cifar_augment
from fedml_tpu.data.synthetic import load_synthetic_images

pytestmark = pytest.mark.slow


def test_crop_flip_cutout_shapes_and_ranges():
    aug = make_cifar_augment(pad=4, cutout_length=16)
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(8, 32, 32, 3)).astype(np.float32)) + 5.0  # strictly positive
    out = aug(x, jax.random.PRNGKey(0))
    assert out.shape == x.shape
    out = np.asarray(out)
    # cutout zeros a box per sample: every sample has some exact zeros
    # (either from the cutout box or the crop's zero padding)
    assert all((out[b] == 0).any() for b in range(8))
    # but not everything is zeroed
    assert (out != 0).mean() > 0.5


def test_cutout_box_clipped_at_border():
    # cutout-only: box centered anywhere must zero between (L/2)^2 (corner)
    # and L^2 (interior) pixels -- the reference's clip semantics
    aug = make_cifar_augment(pad=0, cutout_length=8, hflip=False)
    x = jnp.ones((64, 32, 32, 3))
    out = np.asarray(aug(x, jax.random.PRNGKey(1)))
    zeros = (out[..., 0] == 0).sum(axis=(1, 2))
    assert zeros.min() >= 16 and zeros.max() <= 64
    assert (zeros == 64).any()  # interior boxes exist at B=64


def test_flip_only_is_exact_mirror():
    aug = make_cifar_augment(pad=0, cutout_length=0, hflip=True)
    x = jnp.asarray(np.random.default_rng(2).normal(
        size=(16, 8, 8, 3)).astype(np.float32))
    out = np.asarray(aug(x, jax.random.PRNGKey(3)))
    xn = np.asarray(x)
    for b in range(16):
        same = np.allclose(out[b], xn[b])
        mirrored = np.allclose(out[b], xn[b, :, ::-1, :])
        assert same or mirrored
    # with 16 samples both outcomes occur w.h.p.
    flips = [not np.allclose(out[b], xn[b]) for b in range(16)]
    assert any(flips) and not all(flips)


def test_augmentation_changes_training_not_eval():
    """aug-on must alter the training trajectory; aug-off must leave the
    engine bit-identical to a spec without the hook (VERDICT round-2
    item 3 done-criterion)."""
    dataset = load_synthetic_images(client_num=4, n_train=256, n_test=64,
                                    image_size=16, partition="homo", seed=0)
    model = models.CNNOriginalFedAvg(only_digits=True)
    ex = jnp.zeros((1, 16, 16, 3))

    def run(augment_fn):
        spec = make_classification_spec(model, ex, augment_fn=augment_fn)
        args = types.SimpleNamespace(
            client_num_in_total=4, client_num_per_round=4, comm_round=2,
            epochs=1, batch_size=32, lr=0.05, wd=0.0, client_optimizer="sgd",
            frequency_of_the_test=100, seed=0, device_resident=False)
        api = FedAvgAPI(dataset, spec, args)
        api.train_one_round()
        return jax.tree.leaves(api.global_state["params"])

    base = run(None)
    noop = run(lambda x, rng: x)  # hook wired but identity
    auged = run(make_cifar_augment(pad=2, cutout_length=4))
    for a, b in zip(base, noop):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert any(not np.allclose(np.asarray(a), np.asarray(b), atol=1e-7)
               for a, b in zip(base, auged))


def test_wave_path_applies_augmentation():
    """The device-resident wave path must route batches through
    augment_fn too."""
    dataset = load_synthetic_images(client_num=4, n_train=256, n_test=64,
                                    image_size=16, partition="homo", seed=0)
    model = models.CNNOriginalFedAvg(only_digits=True)
    ex = jnp.zeros((1, 16, 16, 3))

    def run(augment_fn):
        spec = make_classification_spec(model, ex, augment_fn=augment_fn)
        args = types.SimpleNamespace(
            client_num_in_total=4, client_num_per_round=4, comm_round=2,
            epochs=1, batch_size=32, lr=0.05, wd=0.0, client_optimizer="sgd",
            frequency_of_the_test=100, seed=0, device_resident="auto",
            wave_mode=1, client_chunk=2)
        api = FedAvgAPI(dataset, spec, args)
        assert api.runner.mode == "waves"
        api.train_one_round()
        return jax.tree.leaves(api.global_state["params"])

    base = run(None)
    auged = run(make_cifar_augment(pad=2, cutout_length=4))
    assert any(not np.allclose(np.asarray(a), np.asarray(b), atol=1e-7)
               for a, b in zip(base, auged))
