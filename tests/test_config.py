"""Config loading + mesh-shape resolution."""

import pytest

from dtc_tpu.config.loader import load_config, load_yaml_dataclass
from dtc_tpu.config.schema import MeshConfig, ModelConfig, TrainConfig
from dtc_tpu.parallel.mesh import resolve_mesh_shape


def test_load_reference_compatible_yaml(tmp_path):
    # The reference's train-config fields load unchanged
    # (cf. /root/reference/configs/train_config_pp.yaml).
    p = tmp_path / "t.yaml"
    p.write_text(
        "seed: 0\nparallel: pp\nbatch: 8\nsteps: 5000\nlog_every: 50\n"
        "output_dir: outputs/pp\npp_microbatches: 2\n"
    )
    cfg = load_yaml_dataclass(p, TrainConfig)
    assert cfg.parallel == "pp" and cfg.pp_microbatches == 2


def test_unknown_key_raises(tmp_path):
    p = tmp_path / "t.yaml"
    p.write_text("seed: 0\nparallel: dp\nbatch: 8\nsteps: 1\nlog_every: 1\noutput_dir: o\ntypo_key: 1\n")
    with pytest.raises(ValueError, match="typo_key"):
        load_yaml_dataclass(p, TrainConfig)


def test_nested_mesh_key(tmp_path):
    p = tmp_path / "t.yaml"
    p.write_text(
        "seed: 0\nparallel: 3d\nbatch: 8\nsteps: 1\nlog_every: 1\noutput_dir: o\n"
        "mesh:\n  pipe: 2\n  data: 2\n  model: 2\n"
    )
    cfg = load_yaml_dataclass(p, TrainConfig)
    assert (cfg.mesh.pipe, cfg.mesh.data, cfg.mesh.model) == (2, 2, 2)


def test_repo_configs_load():
    train_cfg, model_cfg, opt_cfg = load_config("configs/train_config_dp.yaml")
    assert model_cfg.d_model == 512 and model_cfg.n_layers == 12
    assert opt_cfg.lr == pytest.approx(3e-4)
    # The 3d example is DP×FSDP×TP with overlapped collectives (ISSUE 12;
    # the PP example lives in train_config_pp.yaml).
    t3, _, _ = load_config("configs/train_config_3d.yaml")
    assert t3.parallel == "fsdp" and t3.collectives == "overlapped"
    assert (t3.mesh.data, t3.mesh.model) == (4, 2)
    # Long-context example: sweep-tuned asymmetric fwd/bwd flash tilings.
    _, mlc, _ = load_config(
        "configs/train_config_longctx.yaml",
        model_config_path="configs/model_config_longctx.yaml",
    )
    assert mlc.max_seq_len == 4096 and mlc.attention_block_kv == 1024
    assert mlc.attention_block_kv_bwd == 512
    assert mlc.remat_mode == "block_save_flash"


def test_model_config_validation():
    with pytest.raises(ValueError):
        ModelConfig(vocab_size=10, d_model=10, n_layers=1, n_heads=3, d_ff=4, max_seq_len=8)


def test_attention_block_sizes_must_be_positive():
    """Round-5 ADVICE: a negative block size used to pass
    flash_attention.supports() (Python's modulo of a negative is
    non-negative) and die deep inside pallas_call as an opaque Mosaic
    error; config construction must reject it instead."""
    base = dict(vocab_size=97, d_model=64, n_layers=2, n_heads=4, d_ff=128,
                max_seq_len=32)
    for kw in (
        {"attention_block_q": -512},
        {"attention_block_q": 0},
        {"attention_block_kv": -128},
        {"attention_block_q_bwd": -1},
        {"attention_block_kv_bwd": -256},
    ):
        with pytest.raises(ValueError, match="attention_block"):
            ModelConfig(**base, **kw)
    # 0 stays legal for the bwd overrides: it means "same as forward".
    cfg = ModelConfig(**base, attention_block_q_bwd=0, attention_block_kv_bwd=0)
    assert cfg.attention_block_q_bwd == 0


def test_resolve_mesh_shapes():
    m = MeshConfig()
    assert resolve_mesh_shape("dp", 8, m) == (1, 8, 1)
    assert resolve_mesh_shape("tp", 8, m) == (1, 1, 8)
    assert resolve_mesh_shape("pp", 8, m) == (8, 1, 1)
    assert resolve_mesh_shape("none", 1, m) == (1, 1, 1)
    assert resolve_mesh_shape("3d", 8, MeshConfig(pipe=2, data=2, model=2)) == (2, 2, 2)
    # dp with an explicit tp factor: dp absorbs the rest
    assert resolve_mesh_shape("dp", 8, MeshConfig(model=2)) == (1, 4, 2)
    with pytest.raises(ValueError):
        resolve_mesh_shape("3d", 8, MeshConfig(pipe=2, data=2, model=1))


def test_grad_clip_zero_disables_clipping():
    """grad_clip=0 must mean 'no clipping', not clip-everything-to-zero
    (optax.clip_by_global_norm(0.0) zeroes all gradients)."""
    import jax.numpy as jnp
    import optax

    from dtc_tpu.config.schema import OptimConfig
    from dtc_tpu.train.optimizer import create_optimizer

    tx = create_optimizer(OptimConfig(lr=1.0, weight_decay=0.0, grad_clip=0.0))
    params = {"w": jnp.ones(4)}
    grads = {"w": jnp.full(4, 100.0)}
    state = tx.init(params)
    updates, _ = tx.update(grads, state, params)
    # Adam normalizes: update magnitude ~lr regardless, but with clip(0.0)
    # the update would be exactly zero.
    assert float(jnp.abs(updates["w"]).sum()) > 0


# ---- compile cache placement (utils/dist.configure_compile_cache) ---------


@pytest.fixture
def _restore_cache_dir():
    import jax

    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_left_to_the_environment(monkeypatch, tmp_path, _restore_cache_dir):
    """With JAX_COMPILATION_CACHE_DIR set the helper configures nothing:
    JAX reads the variable itself, and the cache stays placeable from
    outside."""
    import jax

    from dtc_tpu.utils.dist import configure_compile_cache

    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "elsewhere"))
    configure_compile_cache()
    assert jax.config.jax_compilation_cache_dir is None


def test_compile_cache_fixed_path_inside_checkout(monkeypatch, tmp_path, _restore_cache_dir):
    """Unset, the helper names ONE directory inside the checkout — the same
    on every call and from every working directory (a path that moves
    never hits)."""
    import os

    import jax

    from dtc_tpu.utils.dist import configure_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    seen = []
    for cwd in (repo, str(tmp_path)):
        monkeypatch.chdir(cwd)
        jax.config.update("jax_compilation_cache_dir", None)
        configure_compile_cache()
        seen.append(jax.config.jax_compilation_cache_dir)
    assert seen == [os.path.join(repo, ".jax_cache")] * 2
