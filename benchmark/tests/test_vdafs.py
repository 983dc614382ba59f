"""A VDAF family is one file of ``benchmark/vdafs/``: what the three entry
points gave before the move they give after it, a file put on the loader's
path is served with no edit anywhere, and no family file leans on the program.
"""

import ast
import importlib
import json
import os
import random
import textwrap

import pytest

import protocol_bytes
import vdafs
from loadgen import measurements
from reference import plain_aggregate

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

HIST = {"type": "Prio3Histogram", "length": 1024, "chunk_length": 34}
COUNT = {"type": "Prio3Count"}


def committed_vdafs():
    """Every ``vdaf`` a committed configuration names, its rehearsal's too."""
    out = []
    for name in sorted(os.listdir(os.path.join(BENCH, "configs"))):
        with open(os.path.join(BENCH, "configs", name)) as f:
            config = json.load(f)
        for vdaf in (config["vdaf"], config.get("rehearse", {}).get("vdaf")):
            if vdaf is not None and vdaf not in out:
                out.append(vdaf)
    return out


def family_files():
    return sorted(
        f for f in os.listdir(os.path.dirname(vdafs.__file__))
        if f.endswith(".py") and f != "__init__.py"
    )


# -- golden values, taken from the parent of the move (PR 26's tree) -----------


@pytest.mark.parametrize(
    "vdaf, first_twenty",
    [
        (HIST, [948, 764, 547, 283, 381, 13, 692, 949, 165, 684,
                83, 776, 346, 925, 865, 321, 344, 487, 105, 227]),
        (COUNT, [1, 1, 1, 0, 0, 0, 1, 1, 0, 1, 0, 1, 0, 1, 1, 0, 0, 0, 0, 0]),
    ],
    ids=["histogram_1024_34", "count"],
)
def test_measurements_are_the_parents_draw_for_draw(vdaf, first_twenty):
    assert measurements(vdaf, 20, random.Random(9)) == first_twenty
    # one draw a report: the generator that follows is where the parent's was
    rng, ref = random.Random(9), random.Random(9)
    measurements(vdaf, 20, rng)
    for _ in range(20):
        ref.randrange(vdaf.get("length", 2))
    assert rng.getrandbits(64) == ref.getrandbits(64)


@pytest.mark.parametrize(
    "vdaf, lengths, bytes_per_report",
    [(HIST, (16, 1024, 1024, 2, 131, 70), 55953), (COUNT, (8, 1, 1, 0, 5, 4), 241)],
    ids=["histogram_1024_34", "count"],
)
def test_lengths_and_protocol_bytes_are_the_parents(vdaf, lengths, bytes_per_report):
    assert protocol_bytes.flp_lengths(vdaf) == lengths
    assert protocol_bytes.prepare_bytes_per_report(vdaf) == bytes_per_report


# -- the loader ---------------------------------------------------------------


def test_a_family_file_on_the_loaders_path_is_served_by_all_three_entry_points(
    tmp_path, monkeypatch
):
    (tmp_path / "prio3parity.py").write_text(textwrap.dedent(
        """
        from . import gadget_lengths

        def measurements(vdaf, n, rng):
            return [rng.randrange(vdaf["modulus"]) for _ in range(n)]

        def plain_aggregate(vdaf, measurements):
            return sum(measurements) % vdaf["modulus"]

        def flp_lengths(vdaf):
            proof, verifier = gadget_lengths(2, 2, 3)
            return 8, 1, 1, 0, proof, verifier
        """
    ))
    monkeypatch.setattr(vdafs, "__path__", [*vdafs.__path__, str(tmp_path)])
    importlib.invalidate_caches()
    desc = {"type": "Prio3Parity", "modulus": 5}
    ref = random.Random(4)
    assert measurements(desc, 30, random.Random(4)) == [ref.randrange(5) for _ in range(30)]
    assert plain_aggregate(desc, [4, 4, 3]) == 1
    # Mul, 3 calls -> P = 4: proof 2 + 2 * 3 + 1, verifier 1 + 2 + 1
    assert protocol_bytes.flp_lengths(desc) == (8, 1, 1, 0, 9, 4)
    assert protocol_bytes.prepare_bytes_per_report(desc) > 0
    assert vdafs.family(desc) is vdafs.family({"type": "prio3parity"})


@pytest.mark.parametrize("kind", ["Prio3SumVec", "Poplar1", "__init__", "os.path", ""])
@pytest.mark.parametrize(
    "entry",
    [
        lambda d: measurements(d, 1, random.Random(1)),
        lambda d: plain_aggregate(d, [0]),
        protocol_bytes.flp_lengths,
    ],
    ids=["measurements", "plain_aggregate", "flp_lengths"],
)
def test_an_unknown_type_raises_and_names_the_missing_file(entry, kind):
    with pytest.raises(ValueError, match=kind.lower().replace(".", r"\.") + r"\.py"):
        entry({"type": kind})


def test_a_family_file_that_fails_to_import_is_not_reported_as_missing(tmp_path, monkeypatch):
    (tmp_path / "prio3broken.py").write_text("import a_module_nobody_has\n")
    monkeypatch.setattr(vdafs, "__path__", [*vdafs.__path__, str(tmp_path)])
    importlib.invalidate_caches()
    with pytest.raises(ModuleNotFoundError, match="a_module_nobody_has"):
        vdafs.family({"type": "Prio3Broken"})


# -- what the directory holds -------------------------------------------------


def test_every_committed_vdaf_has_its_family_and_every_family_a_configuration():
    named = {v["type"].lower() + ".py" for v in committed_vdafs()}
    # no family that no configuration uses (review of PR 23)
    assert named == set(family_files())
    for vdaf in committed_vdafs():
        module = vdafs.family(vdaf)
        for entry in ("measurements", "plain_aggregate", "flp_lengths"):
            assert callable(getattr(module, entry)), (vdaf["type"], entry)


@pytest.mark.parametrize("name", ["__init__.py", *family_files()])
def test_no_file_of_the_directory_imports_the_program_jax_or_numpy(name):
    with open(os.path.join(os.path.dirname(vdafs.__file__), name)) as f:
        tree = ast.parse(f.read())
    roots = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            roots |= {alias.name.split(".")[0] for alias in node.names}
        elif isinstance(node, ast.ImportFrom):
            # ``from . import x`` stays inside the directory
            roots.add((node.module or "").split(".")[0] if node.level == 0 else "")
    assert not roots & {"janus_tpu", "jax", "jaxlib", "numpy"}, (name, sorted(roots))
