"""The models' shared blocks (``paddle_tpu/models/blocks.py``): no model
file reaches into another's private names, and the parameters of every
model built on the blocks keep the paths, shapes and order the
benchmark's families read them by.
"""

import ast
import hashlib
import json
import os

import pytest

from paddle_tpu import models

MODELS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "paddle_tpu", "models")


def _sibling(node: ast.ImportFrom):
    """The module of this package ``node`` imports from, or None."""
    if node.level == 1:
        return node.module or ""
    prefix = "paddle_tpu.models"
    if node.level == 0 and (node.module or "").startswith(prefix + "."):
        return node.module[len(prefix) + 1:]
    return None


def test_no_model_file_imports_a_private_name_of_another():
    reached = []
    for name in sorted(os.listdir(MODELS)):
        if not name.endswith(".py"):
            continue
        with open(os.path.join(MODELS, name)) as f:
            tree = ast.parse(f.read(), name)
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and \
                    _sibling(node) is not None:
                reached += [f"{name}: {_sibling(node)}.{alias.name}"
                            for alias in node.names
                            if alias.name.startswith("_")]
    assert reached == []


# sha256 of the JSON of [[path, shape], ...] in the order of
# named_parameters(), and its length, as the models had them before their
# shared blocks moved into blocks.py
PARAMETERS = {
    "keye_lm_tiny": (
        models.KeyeLMForCausalLM, 37,
        "d911669817936662ec55bda26c21181a699874fe4f55670bab24b947b4c05b15"),
    "granite_hybrid_tiny": (
        models.GraniteHybridForCausalLM, 34,
        "dfa8006196e078b6882966ba809cbab88dba9c7ec6ff03747de0941510e2c400"),
    "nemotron_h_tiny": (
        models.NemotronHForCausalLM, 29,
        "01ec7988c3180ed3563ffb238b0b70263af234d163084f88070fde9ad39c37f9"),
    "sambay_tiny": (
        models.SambaYForCausalLM, 86,
        "cb2d3723b459ba8d7da6c1cdc8bcfc4d83ce5fc749850b53b1c577025bad5513"),
    "lfm2_moe_tiny": (
        models.Lfm2MoeForCausalLM, 40,
        "a494c865bce391b617dc60b8a0bbddb56304f52e4fb13bf8818d11893fbd6948"),
}


@pytest.mark.parametrize("preset", sorted(PARAMETERS))
def test_tiny_parameters_keep_their_paths_shapes_and_order(preset):
    cls, count, digest = PARAMETERS[preset]
    net = cls(getattr(models, preset)())
    listed = [[n, list(p.shape)] for n, p in net.named_parameters()]
    assert len(listed) == count, listed
    assert hashlib.sha256(json.dumps(listed).encode()).hexdigest() == \
        digest, listed
