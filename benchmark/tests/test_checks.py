"""The training checks pass the program's own step and fail a wrong one."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmark import checks, harness
from benchmark.drivers import train


@pytest.fixture(scope="module")
def first_step():
    """One step of ``cub200-train``'s tiny twin, and what it started from."""
    cell = harness.load_cell("cub200-train", rehearse=True)
    dalle_cfg, vae_cfg = harness.build_configs(cell.config)
    b = train.build(cell, jax.devices()[:1], dalle_cfg, vae_cfg)
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    params, vae_params = b["init_dalle"](keys[0]), b["init_vae"](keys[1])
    size = vae_cfg.image_size
    images = [jax.random.uniform(k, (4, size, size, 3)) for k in keys[2:]]
    text = jnp.asarray(harness.make_prompts(cell, dalle_cfg, 4, 0))
    start = jax.tree.map(jnp.copy, params)
    new, _, loss = b["step"](params, b["tx"].init(params), vae_params, text,
                             images[0], jax.random.PRNGKey(5))
    given = dict(dalle_cfg=dalle_cfg, vae=b["vae"], vae_cfg=vae_cfg,
                 start_params=start, vae_params=vae_params, text=text,
                 images=images[0], step_loss=loss, micro=2,
                 update=jax.tree.map(jnp.subtract, new, start))
    return given, images[1]


def test_reference_step_passes_the_programs_step(first_step):
    given = first_step[0]
    out = checks.reference_step(**given)
    assert out["ok"], out
    assert out["vae_codes_share"] >= 0.99 and out["loss_err"] < 1e-3


@pytest.mark.parametrize("fault", ["other_images", "other_captions",
                                   "no_caption", "leaf_not_updated",
                                   "update_negated", "loss_shifted"])
def test_reference_step_fails_a_wrong_step(first_step, fault):
    given, other_images = first_step
    wrong = {
        "other_images": {"images": other_images},
        "other_captions": {"text": jnp.roll(given["text"], 1, axis=0)},
        "no_caption": {"text": jnp.zeros_like(given["text"])},
        "leaf_not_updated": {"update": dict(
            given["update"], final_norm=jax.tree.map(
                jnp.zeros_like, given["update"]["final_norm"]))},
        "update_negated": {"update": jax.tree.map(jnp.negative,
                                                  given["update"])},
        "loss_shifted": {"step_loss": given["step_loss"] + 0.01},
    }[fault]
    out = checks.reference_step(**{**given, **wrong})
    assert not out["ok"], out


def test_replicas_agree_finds_the_copy_that_differs():
    devices = jax.devices()[:4]
    if len(devices) < 2:
        pytest.skip("needs several devices (conftest asks for four)")
    mesh = Mesh(np.array(devices), ("dp",))
    replicated = NamedSharding(mesh, P())
    same = jax.device_put(jnp.arange(8.0), replicated)
    assert checks.replicas_agree(mesh, {"w": same})["ok"]
    copies = [jax.device_put(jnp.arange(8.0) + (i == 1), d)
              for i, d in enumerate(devices)]
    differs = jax.make_array_from_single_device_arrays((8,), replicated,
                                                       copies)
    out = checks.replicas_agree(mesh, {"w": same, "v": differs})
    assert not out["ok"] and out["differing_leaves"] == 1
