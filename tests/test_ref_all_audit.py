"""Reference-wide export audit: every name declared in ANY ``__all__``
across the reference's python/paddle tree must resolve on the mapped
paddle_tpu namespace. This is the line-by-line completeness check for
SURVEY.md §2 — a name may resolve to a working implementation OR to a
recorded-descope raiser (the import must succeed either way; §4b
descopes are about behavior, not import errors).
"""
import ast
import importlib
import pathlib
import warnings

import pytest

REF = pathlib.Path("/root/reference/python/paddle")

# reference-side __all__ entries that are not real export names
_REF_ARTIFACTS = {
    # conll05.py __all__ has a malformed entry 'test, get_dict' (one
    # string); the audit splits it, nothing to skip beyond that
}


def _harvest():
    out = []
    for py in sorted(REF.rglob("*.py")):
        rel = py.relative_to(REF)
        if {"tests", "proto", "libs"} & set(rel.parts):
            continue
        try:
            with warnings.catch_warnings():
                # the reference's own docstrings carry invalid escape
                # sequences; their SyntaxWarnings aren't ours to fix
                warnings.simplefilter("ignore", SyntaxWarning)
                tree = ast.parse(py.read_text())
        except SyntaxError:
            continue
        names = []
        for node in tree.body:
            target = None
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name) and t.id == "__all__":
                        target = node.value
            elif isinstance(node, ast.AugAssign) and \
                    isinstance(node.target, ast.Name) and \
                    node.target.id == "__all__":
                target = node.value
            if target is not None:
                try:
                    vals = ast.literal_eval(target)
                except ValueError:
                    continue
                for v in vals:
                    # a reference-side typo packs several names in one
                    # string ('test, get_dict' in dataset/conll05.py)
                    names.extend(x.strip() for x in v.split(","))
        names = [n for n in names if n and not n.startswith("_")
                 and n not in _REF_ARTIFACTS]
        if names:
            out.append((str(rel), names))
    return out


def _candidates(path):
    """Namespaces a reference module's exports may resolve on: the
    same dotted path (module import OR attribute chain), each parent,
    and the flat fluid/top-level namespaces the reference star-imports
    into."""
    mods = []

    def by_import(name):
        try:
            mods.append(importlib.import_module(name))
            return True
        except ImportError:
            return False

    def by_attr_chain(dotted):
        parts = dotted.split(".")
        for i in range(len(parts), 0, -1):
            try:
                obj = importlib.import_module(".".join(parts[:i]))
            except ImportError:
                continue
            try:
                for attr in parts[i:]:
                    obj = getattr(obj, attr)
            except AttributeError:
                continue
            mods.append(obj)
            return True
        return False

    dotted = "paddle_tpu." + path[:-3].replace("/", ".")
    if dotted.endswith(".__init__"):
        dotted = dotted[: -len(".__init__")]
    by_import(dotted) or by_attr_chain(dotted)
    parts = dotted.split(".")
    for i in range(len(parts) - 1, 1, -1):
        prefix = ".".join(parts[:i])
        by_import(prefix) or by_attr_chain(prefix)
    if path.startswith("fluid/"):
        # ref fluid/__init__ star-imports framework/executor/layers
        for extra in ("paddle_tpu.fluid", "paddle_tpu.fluid.layers"):
            by_import(extra)
    if path.startswith(("nn/", "tensor/", "framework/")):
        by_import("paddle_tpu.nn")
        by_import("paddle_tpu.nn.functional")
    by_import("paddle_tpu")
    return mods


def test_every_reference_export_resolves():
    if not REF.is_dir():
        pytest.skip(f"the reference tree is not mounted here: no {REF}")
    report = _harvest()
    assert len(report) > 100, "harvest looks broken"
    total = sum(len(names) for _, names in report)
    assert total > 700, "harvest looks broken"
    missing = []
    for path, names in report:
        cands = _candidates(path)
        for n in names:
            if not any(hasattr(m, n) for m in cands):
                missing.append(f"{path}: {n}")
    assert not missing, (
        f"{len(missing)}/{total} reference exports unresolved:\n"
        + "\n".join(missing))


def test_new_dataset_helpers_behave():
    """The audit's last closures are real: image loaders decode, the
    tar batcher writes batches, movielens info tables agree with the
    readers' id spaces."""
    import io
    import pickle
    import tarfile
    import tempfile

    import numpy as np
    from PIL import Image

    import paddle_tpu.dataset as D

    # image loaders
    img = Image.fromarray(
        (np.arange(48).reshape(4, 4, 3) * 5).astype("uint8"))
    buf = io.BytesIO()
    img.save(buf, format="PNG")
    arr = D.image.load_image_bytes(buf.getvalue())
    assert arr.shape == (4, 4, 3)
    gray = D.image.load_image_bytes(buf.getvalue(), is_color=False)
    assert gray.shape == (4, 4)

    with tempfile.TemporaryDirectory() as d:
        import os

        p = os.path.join(d, "im.png")
        img.resize((40, 40)).save(p)
        out = D.image.load_and_transform(p, 32, 24, is_train=False)
        assert out.shape == (3, 24, 24)

        # tar batcher
        tar_path = os.path.join(d, "imgs.tar")
        with tarfile.open(tar_path, "w") as tf:
            tf.add(p, arcname="im.png")
        meta = D.image.batch_images_from_tar(
            tar_path, "unit", {"im.png": 7}, num_per_batch=2)
        batch_file = open(meta).read().splitlines()[0]
        blob = pickle.load(open(batch_file, "rb"))
        assert blob["label"] == [7]
        assert D.image.load_image_bytes(blob["data"][0]).ndim == 3

    # movielens info tables
    ui = D.movielens.user_info()
    mi = D.movielens.movie_info()
    assert len(ui) == D.movielens.max_user_id()
    assert max(m.index for m in mi.values()) == D.movielens.max_movie_id()
    first = mi[1].value()
    assert isinstance(first[0], int) and first[1] and first[2]
    assert D.movielens.age_table[0] == 1
    u = ui[1].value()
    assert u[2] < len(D.movielens.age_table)

    # imdb build_dict is the corpus dict
    assert D.imdb.build_dict() == D.imdb.word_dict()
