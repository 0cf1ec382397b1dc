"""Example scripts smoke tests (reference: examples/ are exercised by
tests/L1 clones; here the fast one runs directly)."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_simple_distributed_example_runs():
    env = dict(os.environ, PYTHONPATH=REPO)
    script = os.path.join(REPO, "examples", "simple", "distributed",
                          "distributed_data_parallel.py")
    # the child inherits JAX_PLATFORMS=cpu and the 8 virtual devices from
    # tests/conftest.py; the config update pins the CPU backend again
    code = (f"import jax; jax.config.update('jax_platforms', 'cpu'); "
            f"import runpy; runpy.run_path({script!r}, "
            f"run_name='__main__')")
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "final loss:" in out.stdout
    final = float(out.stdout.rsplit("final loss:", 1)[1].strip())
    assert final < 0.5


def test_bert_example_runs():
    env = dict(os.environ, PYTHONPATH=REPO)
    script = os.path.join(REPO, "examples", "bert", "main_amp.py")
    code = (f"import jax; jax.config.update('jax_platforms', 'cpu'); "
            f"import sys; sys.argv = ['main_amp.py', '--steps', '6', "
            f"'--batch', '4', '--seq-len', '32', '--layers', '2', "
            f"'--hidden', '64', '--heads', '4', '--print-freq', '2']; "
            f"import runpy; runpy.run_path({script!r}, "
            f"run_name='__main__')")
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "final loss:" in out.stdout
    assert "seq/s" in out.stdout


def test_dcgan_fused_example_runs():
    env = dict(os.environ, PYTHONPATH=REPO)
    script = os.path.join(REPO, "examples", "dcgan", "main_amp.py")
    code = (f"import jax; jax.config.update('jax_platforms', 'cpu'); "
            f"import sys; sys.argv = ['main_amp.py', '--fused', "
            f"'--iters', '3', '--batch-size', '4', '--opt-level', 'O2']; "
            f"import runpy; runpy.run_path({script!r}, "
            f"run_name='__main__')")
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "Loss_D" in out.stdout and "Loss_G" in out.stdout


def test_gpt_example_runs():
    env = dict(os.environ, PYTHONPATH=REPO)
    script = os.path.join(REPO, "examples", "gpt", "main_amp.py")
    code = (f"import jax; jax.config.update('jax_platforms', 'cpu'); "
            f"import sys; sys.argv = ['main_amp.py', '--steps', '6', "
            f"'--batch', '2', '--seq-len', '32', '--layers', '2', "
            f"'--hidden', '64', '--heads', '4', '--print-freq', '2']; "
            f"import runpy; runpy.run_path({script!r}, "
            f"run_name='__main__')")
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "final loss:" in out.stdout


def test_gpt_sp_example_runs():
    """The long-context sequence-parallel example: 8-way ring on the
    virtual CPU mesh, remat on, loss finite and improving."""
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    script = os.path.join(REPO, "examples", "gpt", "main_sp.py")
    out = subprocess.run(
        [sys.executable, script, "--devices", "8", "--seq-len", "128",
         "--steps", "12", "--layers", "2", "--hidden", "64", "--heads",
         "4", "--vocab", "97", "--batch", "2", "--lr", "1e-2",
         "--print-freq", "5"],
        capture_output=True, text=True, timeout=500, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "ring of 8" in out.stdout
    final = float(out.stdout.rsplit("final loss:", 1)[1].strip())
    import math
    # fresh random tokens each step: loss hovers near ln(vocab); just
    # prove the ring step runs and stays numerically sane
    assert math.isfinite(final) and final < math.log(97) + 1.0


def test_gpt_moe_example_runs():
    """The Switch-MoE example: 4 experts on the data axis of a virtual
    CPU mesh, top-2 routing, aux loss in the optimized loss."""
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = os.path.join(REPO, "examples", "gpt", "main_moe.py")
    out = subprocess.run(
        [sys.executable, script, "--devices", "4", "--steps", "10",
         "--seq-len", "32", "--layers", "2", "--hidden", "64", "--heads",
         "4", "--vocab", "97", "--batch", "4", "--lr", "1e-2",
         "--top-k", "2", "--print-freq", "5"],
        capture_output=True, text=True, timeout=500, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MoE blocks, top-2" in out.stdout
    final = float(out.stdout.rsplit("final loss:", 1)[1].strip())
    import math
    # loss includes the aux term (~aux_weight above the task loss)
    assert math.isfinite(final) and final < math.log(97) + 1.0


def test_gpt_tp_example_runs():
    """The data x tensor parallel example: (2, 4) mesh on the virtual CPU
    backend, Megatron head/MLP sharding, loss finite and sane."""
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    script = os.path.join(REPO, "examples", "gpt", "main_tp.py")
    out = subprocess.run(
        [sys.executable, script, "--dp", "2", "--tp", "4", "--steps", "12",
         "--seq-len", "32", "--layers", "2", "--hidden", "64", "--heads",
         "4", "--vocab", "97", "--batch", "4", "--lr", "1e-2",
         "--print-freq", "5"],
        capture_output=True, text=True, timeout=500, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "mesh 2x4 (data x tp)" in out.stdout
    final = float(out.stdout.rsplit("final loss:", 1)[1].strip())
    import math
    assert math.isfinite(final) and final < math.log(97) + 1.0


def test_llama_example_runs():
    """Train + prefill generate + int8 self-draft speculative decode in
    one script; the script itself asserts speculative == greedy."""
    env = dict(os.environ, PYTHONPATH=REPO)
    script = os.path.join(REPO, "examples", "llama", "main.py")
    code = (f"import jax; jax.config.update('jax_platforms', 'cpu'); "
            f"import sys; sys.argv = ['main.py', '--steps', '6', "
            f"'--batch', '2', '--seq-len', '32', '--layers', '2', "
            f"'--hidden', '64', '--heads', '4', '--kv-heads', '2', "
            f"'--gen-tokens', '8', '--print-freq', '2']; "
            f"import runpy; runpy.run_path({script!r}, "
            f"run_name='__main__')")
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "matches greedy exactly" in out.stdout


def test_llama_lora_example_runs():
    """LoRA fine-tune example: factors-only training, merge, and the
    merged-decode assertion inside the script."""
    env = dict(os.environ, PYTHONPATH=REPO)
    script = os.path.join(REPO, "examples", "llama", "main_lora.py")
    code = (f"import jax; jax.config.update('jax_platforms', 'cpu'); "
            f"import sys; sys.argv = ['main_lora.py', '--steps', '6', "
            f"'--batch', '2', '--seq-len', '32', '--layers', '2', "
            f"'--hidden', '64', '--rank', '4', '--print-freq', '2']; "
            f"import runpy; runpy.run_path({script!r}, "
            f"run_name='__main__')")
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "merged: decode identical" in out.stdout
    assert "trainable:" in out.stdout


def test_llama_tp_serve_example_runs():
    """TP serving demo: sharded greedy decode bit-identical to
    single-shard, int8 under TP, and TP-target speculative decoding —
    the script itself asserts all three."""
    env = dict(os.environ, PYTHONPATH=REPO,
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    script = os.path.join(REPO, "examples", "llama", "main_tp_serve.py")
    code = (f"import jax; jax.config.update('jax_platforms', 'cpu'); "
            f"import sys; sys.argv = ['main_tp_serve.py', '--tp', '2', "
            f"'--new-tokens', '12', '--hidden', '64', '--layers', '2']; "
            f"import runpy; runpy.run_path({script!r}, "
            f"run_name='__main__')")
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "bit-identical to single-shard: True" in out.stdout
    assert "exact match with tp int8 decode: True" in out.stdout
    assert "tp beam search (3 beams): bit-identical to single-shard: " \
        "True" in out.stdout


def test_imagenet_channels_last_example_runs(tmp_path):
    """The flagship example's NHWC arm: to_channels_last model + the
    layout-preserving prefetcher train end-to-end (tiny synthetic).
    ONE device: the eager DDP loop's per-op compiles desynchronize
    multi-device rendezvous on a single CPU core (40s timeout); DDP
    collectives are covered by the fused-step and distributed suites —
    this test is about the layout path."""
    env = dict(os.environ, PYTHONPATH=REPO,
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    script = os.path.join(REPO, "examples", "imagenet", "main_amp.py")
    code = (f"import jax; jax.config.update('jax_platforms', 'cpu'); "
            f"import sys; sys.argv = ['main_amp.py', '--synthetic', "
            f"'--channels-last', '-a', 'resnet18', '-b', '8', "
            f"'--image-size', '32', '--iters-per-epoch', '4', "
            f"'--print-freq', '2', "
            f"'--checkpoint', {str(tmp_path / 'ck.pkl')!r}]; "
            f"import runpy; runpy.run_path({script!r}, "
            f"run_name='__main__')")
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "img/s" in out.stdout or "loss" in out.stdout.lower()


def test_gpt_session_example_runs():
    """The serving-session demo: multi-turn int8 chat with the one-shot
    exactness assertion inside the script."""
    env = dict(os.environ, PYTHONPATH=REPO)
    script = os.path.join(REPO, "examples", "gpt", "main_session.py")
    code = (f"import jax; jax.config.update('jax_platforms', 'cpu'); "
            f"import sys; sys.argv = ['main_session.py', '--turns', '2', "
            f"'--reply-tokens', '6', '--hidden', '64']; "
            f"import runpy; runpy.run_path({script!r}, "
            f"run_name='__main__')")
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "equals one-shot decode of the history: True" in out.stdout
