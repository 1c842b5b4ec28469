"""End-user entry points: examples/ scripts and tools/ CLIs.

Parity targets: example/image-classification/train_mnist.py,
benchmark_score.py, tools/im2rec.py, tools/launch.py.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cmd, **env_extra):
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT})
    env.update(env_extra)
    return subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=600)


def test_train_mnist_runs_synthetic():
    r = _run([sys.executable, "examples/image_classification/train_mnist.py",
              "--network", "mlp", "--benchmark", "1", "--batch-size", "32",
              "--num-epochs", "1", "--num-examples", "1280"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "final validation accuracy" in r.stdout


def test_benchmark_score_runs():
    r = _run([sys.executable,
              "examples/image_classification/benchmark_score.py",
              "--networks", "squeezenet1.1", "--batch-sizes", "2",
              "--image-shape", "3,64,64", "--steps", "2"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "img/s" in r.stdout


def test_im2rec_list_and_pack_roundtrip(tmp_path):
    cv2 = pytest.importorskip("cv2")
    root = tmp_path / "imgs"
    for cls in ("cat", "dog"):
        (root / cls).mkdir(parents=True)
        for i in range(3):
            img = np.random.RandomState(i).randint(
                0, 255, (32, 40, 3), np.uint8)
            cv2.imwrite(str(root / cls / ("%d.jpg" % i)), img)
    prefix = str(tmp_path / "pack")
    r = _run([sys.executable, "tools/im2rec.py", prefix, str(root),
              "--list", "--recursive"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert os.path.exists(prefix + ".lst")
    r = _run([sys.executable, "tools/im2rec.py", prefix, str(root),
              "--resize", "28"])
    assert r.returncode == 0, r.stderr[-2000:]

    from mxnet_tpu import recordio
    rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "r")
    labels = set()
    for k in rec.keys:
        header, img = recordio.unpack_img(rec.read_idx(k))
        assert min(img.shape[:2]) == 28
        labels.add(int(header.label))
    assert labels == {0, 1}


def test_launch_local_spawns_workers(tmp_path):
    marker = str(tmp_path / "out")
    script = tmp_path / "worker.py"
    script.write_text(
        "import os\n"
        "open(%r + os.environ['MXNET_TPU_PROC_ID'], 'w')"
        ".write(os.environ['MXNET_TPU_NUM_PROC'])\n" % marker)
    r = _run([sys.executable, "tools/launch.py", "-n", "3",
              sys.executable, str(script)])
    assert r.returncode == 0, r.stderr[-2000:]
    for i in range(3):
        assert open(marker + str(i)).read() == "3"


def test_model_parallel_matrix_factorization_runs():
    r = _run([sys.executable,
              "examples/model_parallel/matrix_factorization.py",
              "--num-epochs", "2", "--num-users", "50",
              "--num-items", "30"],
             XLA_FLAGS="--xla_force_host_platform_device_count=2")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "cpu(1)" in r.stdout          # second group really placed
    mse = float(r.stdout.rsplit("mse=", 1)[1])
    assert mse < 5.0


def test_bucketing_lstm_learns():
    r = _run([sys.executable, "examples/rnn/bucketing_lstm.py",
              "--num-epochs", "2", "--buckets", "6,8",
              "--batch-size", "8"])
    assert r.returncode == 0, r.stderr[-2000:]
    ppl = float(r.stdout.rsplit("perplexity=", 1)[1].split()[0])
    assert ppl < 8.0                     # far below the 16-way uniform


def test_parse_log_summarizes_epochs(tmp_path):
    log = tmp_path / "train.log"
    log.write_text(
        "INFO:root:Epoch[0] Batch [20] Speed: 1500.00 samples/sec\n"
        "INFO:root:Epoch[0] Train-accuracy=0.5\n"
        "INFO:root:Epoch[0] Time cost=10.0\n"
        "INFO:root:Epoch[0] Validation-accuracy=0.6\n")
    r = _run([sys.executable, "tools/parse_log.py", str(log)])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "train-accuracy" in r.stdout and "0.6" in r.stdout


def test_diagnose_runs():
    r = _run([sys.executable, "tools/diagnose.py"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "mxnet_tpu" in r.stdout and "Devices" in r.stdout


@pytest.mark.slow
def test_train_imagenet_benchmark_tiny():
    r = _run([sys.executable,
              "examples/image_classification/train_imagenet.py",
              "--benchmark", "1", "--batch-size", "8", "--num-epochs", "1",
              "--num-layers", "18", "--image-shape", "3,32,32",
              "--num-classes", "10", "--num-examples", "64",
              "--disp-batches", "4"])
    assert r.returncode == 0, r.stderr[-2000:]


def test_distributed_training_two_workers(tmp_path):
    """launch.py -n 2: true multi-process dist_tpu_sync — cross-process
    gradient all-reduce through the KVStore API, identical models on
    every rank (example/distributed_training parity)."""
    script = str(tmp_path / "worker.py")
    # exact-sum check through the kvstore API across processes, then a
    # short converging fit via the example
    open(script, "w").write(
        "import sys\n"
        "sys.path.insert(0, %r)\n"
        "import numpy as np\n"
        "from mxnet_tpu import parallel\n"
        "parallel.init_distributed()\n"
        "import mxnet_tpu as mx\n"
        "kv = mx.kvstore.create('dist_tpu_sync')\n"
        "rank, n = kv.rank, kv.num_workers\n"
        "assert n == 2\n"
        "kv.init('3', mx.nd.zeros((4, 3)))\n"
        "kv.push('3', mx.nd.ones((4, 3)) * (rank + 1))\n"
        "out = mx.nd.zeros((4, 3))\n"
        "kv.pull('3', out=out)\n"
        "np.testing.assert_allclose(out.asnumpy(), 3.0)\n"  # 1 + 2
        "print('EXACT-SUM-OK', rank)\n" % os.getcwd())
    r = _run([sys.executable, "tools/launch.py", "-n", "2",
              "--launcher", "local", sys.executable, script])
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.count("EXACT-SUM-OK") == 2

    r = _run([sys.executable, "tools/launch.py", "-n", "2",
              "--launcher", "local", sys.executable,
              "examples/distributed/train_mnist_dist.py",
              "--num-epochs", "3", "--num-samples", "192"])
    assert r.returncode == 0, r.stderr[-2000:]
    accs = [float(line.rsplit("=", 1)[1])
            for line in r.stdout.splitlines()
            if "final validation accuracy" in line]
    assert len(accs) == 2 and min(accs) > 0.9
    # ranks hold identical models -> identical accuracy
    assert abs(accs[0] - accs[1]) < 1e-6


def test_sparse_linear_classification_learns():
    r = _run([sys.executable, "examples/sparse/linear_classification.py",
              "--num-epochs", "8", "--dim", "300",
              "--num-samples", "2048", "--lr", "1.0"])
    assert r.returncode == 0, r.stderr[-2000:]
    acc = float(r.stdout.rsplit("accuracy=", 1)[1])
    assert acc > 0.85


def test_sparse_factorization_machine_learns():
    r = _run([sys.executable, "examples/sparse/factorization_machine.py",
              "--num-epochs", "6", "--dim", "200",
              "--num-samples", "2048"])
    assert r.returncode == 0, r.stderr[-2000:]
    acc = float(r.stdout.rsplit("accuracy=", 1)[1])
    assert acc > 0.7


def test_sparse_wide_deep_learns():
    r = _run([sys.executable, "examples/sparse/wide_deep.py",
              "--num-epochs", "8", "--num-samples", "3072"])
    assert r.returncode == 0, r.stderr[-2000:]
    acc = float(r.stdout.rsplit("accuracy=", 1)[1])
    assert acc > 0.75


@pytest.mark.slow
def test_ssd_detection_learns():
    """End-to-end SSD loop: ImageDetIter -> MultiBoxPrior/Target under
    autograd -> MultiBoxDetection eval (example/ssd parity)."""
    r = _run([sys.executable, "examples/ssd_detection.py",
              "--num-epochs", "12", "--num-samples", "192"])
    assert r.returncode == 0, r.stderr[-2000:]
    acc = float(r.stdout.rsplit("accuracy=", 1)[1])
    assert acc > 0.6


def test_dcgan_learns_distribution():
    """Adversarial loop: generated samples concentrate mass centrally
    like the real blobs (uniform noise would score ~0.25)."""
    # 10 epochs: at 6 the discriminator still dominates on this jax
    # version (lossG ~5, generated energy ~ uniform); by 10 the
    # adversarial balance recovers and generated mass concentrates
    r = _run([sys.executable, "examples/dcgan.py",
              "--num-epochs", "10", "--batches-per-epoch", "12"])
    assert r.returncode == 0, r.stderr[-2000:]
    line = [l for l in r.stdout.splitlines() if "center-energy" in l][-1]
    gen = float(line.rsplit("generated=", 1)[1])
    assert gen > 0.4


def test_long_context_example_matches_dense():
    r = _run([sys.executable, "examples/long_context.py",
              "--seq-len", "1024", "--check"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "MATCHES dense attention" in r.stdout


def test_transformer_lm_example_learns():
    """The flagship SPMD transformer trains on the dp x tp x sp mesh."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    r = subprocess.run([sys.executable, "examples/transformer_lm.py",
                        "--steps", "120"], capture_output=True,
                       text=True, env=env, cwd=os.getcwd(), timeout=540)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "LEARNED" in r.stdout


def test_elastic_training_crash_resume():
    """Failure recovery contract (SURVEY §5: recovery = restart from
    checkpoint): the example crashes a sharded training run mid-flight,
    relaunches the same command line, and the resumed trajectory must
    reproduce the uninterrupted run exactly."""
    r = _run([sys.executable, "examples/elastic_training.py", "--demo"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK: crash + relaunch reproduces" in r.stdout


def test_im2rec_native_matches_python_packer(tmp_path):
    """src/io/im2rec_pack.cc writes byte-identical .rec/.idx to the
    Python packer (same list, same resize/quality)."""
    cv2 = pytest.importorskip("cv2")
    from mxnet_tpu import _native
    if _native.im2rec_lib() is None:
        pytest.skip("OpenCV C++ toolchain unavailable")
    root = tmp_path / "imgs"
    for cls in ("a", "b"):
        (root / cls).mkdir(parents=True)
        for i in range(4):
            img = np.random.RandomState(10 * i).randint(
                0, 255, (48, 36, 3), np.uint8)
            cv2.imwrite(str(root / cls / ("%d.jpg" % i)), img)
    prefix_py = str(tmp_path / "py")
    prefix_cc = str(tmp_path / "cc")
    r = _run([sys.executable, "tools/im2rec.py", prefix_py, str(root),
              "--list", "--recursive"])
    assert r.returncode == 0, r.stderr[-2000:]
    import shutil
    shutil.copy(prefix_py + ".lst", prefix_cc + ".lst")
    r = _run([sys.executable, "tools/im2rec.py", prefix_py, str(root),
              "--resize", "32"])
    assert r.returncode == 0, r.stderr[-2000:]
    r = _run([sys.executable, "tools/im2rec.py", prefix_cc, str(root),
              "--resize", "32", "--num-thread", "4"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "native x4" in r.stdout, r.stdout
    with open(prefix_py + ".rec", "rb") as f:
        py_rec = f.read()
    with open(prefix_cc + ".rec", "rb") as f:
        cc_rec = f.read()
    assert py_rec == cc_rec
    with open(prefix_py + ".idx") as f:
        py_idx = f.read()
    with open(prefix_cc + ".idx") as f:
        cc_idx = f.read()
    assert py_idx == cc_idx


def test_kill_mxnet_local(tmp_path):
    """tools/kill_mxnet.py kills a matching process locally."""
    import getpass
    import time
    marker = "mxtpu_kill_test_%d" % os.getpid()
    victim = subprocess.Popen(
        [sys.executable, "-c",
         "import sys, time; time.sleep(60)  # %s" % marker])
    try:
        r = _run([sys.executable, "tools/kill_mxnet.py", "-",
                  getpass.getuser(), marker])
        assert r.returncode == 0, r.stderr[-2000:]
        deadline = time.time() + 10
        while victim.poll() is None and time.time() < deadline:
            time.sleep(0.1)
        assert victim.poll() is not None
    finally:
        if victim.poll() is None:
            victim.kill()


@pytest.mark.slow
def test_bench_fold_cast_variant_matches():
    """MXNET_FOLD_CAST=1 (persistent bf16 weights, cast folded into the
    optimizer update — the reference's mp_sgd layout) must follow the
    same loss trajectory as the per-step-cast default."""
    script = (
        "import os, sys; sys.path.insert(0, %r)\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "import numpy as np, jax.numpy as jnp\n"
        "import bench\n"
        "step, args, mom, aux = bench.build_train_step(4, 32, classes=10)\n"
        "rng = np.random.RandomState(0)\n"
        "x = jnp.asarray(rng.rand(4, 3, 32, 32).astype('float32'))\n"
        "y = jnp.asarray(rng.randint(0, 10, (4,)), jnp.int32)\n"
        "losses = []\n"
        "for _ in range(3):\n"
        "    args, mom, aux, loss = step(args, mom, aux, x, y)\n"
        "    losses.append(float(loss))\n"
        "print('LOSSES', losses)\n" % ROOT)
    outs = {}
    # pin both sides explicitly: the default is fold-cast ON since the
    # round-5 chip A/B, so an empty env would compare fold vs itself
    for name, env in (("base", {"MXNET_FOLD_CAST": "0"}),
                      ("fold", {"MXNET_FOLD_CAST": "1"})):
        r = _run([sys.executable, "-c", script], **env)
        assert r.returncode == 0, r.stderr[-2000:]
        line = [ln for ln in r.stdout.splitlines()
                if ln.startswith("LOSSES")][0]
        outs[name] = eval(line.split(" ", 1)[1])
    np.testing.assert_allclose(outs["fold"], outs["base"],
                               rtol=1e-5, atol=1e-6)


def test_llm_serving_example():
    """Train-then-serve through the KV-cache decode under the dp/tp
    mesh: greedy generation reproduces the memorized pattern."""
    r = _run([sys.executable, "examples/llm_serving.py"],
             XLA_FLAGS="--xla_force_host_platform_device_count=8")
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-1000:])
    assert "SERVED OK" in r.stdout
    assert "mesh dp=2 tp=2" in r.stdout


@pytest.mark.slow
def test_bandwidth_tool_cross_process():
    """tools/bandwidth.py --num-workers 2: the all-reduce crosses the
    multi-process wire path and the pulled aggregate is the exact
    2-worker sum (rank-0 prints the JSON metric line)."""
    r = _run([sys.executable, "tools/bandwidth.py", "--num-workers", "2",
              "--num-batches", "2"])
    assert r.returncode == 0, (r.stdout[-800:], r.stderr[-1200:])
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith('{"metric"')][-1]
    import json as _json
    rec = _json.loads(line)
    # workers = global device count (2 processes x local devices; the
    # test env may force 8 virtual CPU devices per process)
    assert rec["processes"] == 2 and rec["workers"] % 2 == 0
    assert rec["value"] > 0
    assert "results verified" in r.stderr + r.stdout
