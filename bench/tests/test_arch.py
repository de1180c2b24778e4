"""The architectures under ``arch/`` make the same weights and reference
logits as the harness did before it took them from there, bit for bit.

At each configuration's smoke size on the CPU: a SHA-256 over every leaf
of ``weights.make_params`` (path, dtype, shape and bytes), and over the
reference logits of one fixed prompt under the smoke ``check.act`` and
``check.control``.  The digests were recorded with the harness as it was
before ``arch/`` existed, its family branches inside ``weights.py`` and
``reference.py``.
"""
import ast
import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference
import run
import weights

SEED = 2 ** 33 + 5
DIGESTS = {
    "mamba2-130m-2l.chat": {
        "weights": "cd3089ec5f38ab7fdb7f7a13a4a22d00020788dee554b54448fdb77ccb0ff8c1",
        "bfloat16": "7ac82ba12dbcae141ca2a860afc1a414e6deec28990647cf5409c462785bc142",
        "float8_e4m3fn": "6ef7e006f6496aa17420aa2143f81837a1d0a73f849003381b8706821b697185",
    },
    "minicpm-2b-1l.shared_prefix": {
        "weights": "3ebc8b6ec722b86befe6b66266959d8e322fa16ad3fdf67b0796b3c2280d5eba",
        "bfloat16": "43bce29234c5c1a4f559ca55a496355e1906d9a13b281091f94dd8174b0d2c91",
        "float8_e4m3fn": "82f69ad9927da87abb795a0c9fe257ff8ba91aceef8875be94d3abfeac66f7ba",
    },
}


def digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)}|{a.dtype}|{a.shape}|".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("cell", sorted(DIGESTS))
def test_weights_and_logits_bit_identical(cell):
    spec = run.load_spec(run.REPO, cell, rehearse=True)
    arch, model = spec["arch"], spec["config"]["model"]
    chk = spec["config"]["check"]
    params = weights.make_params(arch, model, SEED)
    got = {"weights": digest(params)}
    toks = np.random.default_rng(7).integers(
        0, model["vocab_size"], 48).astype(np.int32)
    for act in (chk["act"], chk["control"]):
        got[act] = digest(reference.logits(
            params, jnp.asarray(toks), 16, arch=arch,
            model_items=reference.model_items(model), n_out=32, act=act))
    assert got == DIGESTS[cell]


def test_arch_modules_import_nothing_of_the_program():
    arch_dir = os.path.join(run.BENCH_DIR, "arch")
    names = sorted(f for f in os.listdir(arch_dir) if f.endswith(".py"))
    assert names
    for name in names:
        with open(os.path.join(arch_dir, name)) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                mods = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                mods = [node.module or ""]
            else:
                continue
            for mod in mods:
                assert mod.split(".")[0] not in ("repro", "src"), (name, mod)
