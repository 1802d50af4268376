import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

_REPO = pathlib.Path(__file__).resolve().parents[2]
for p in (str(_REPO), str(_REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402


@pytest.fixture
def small_root(tmp_path):
    from benchkit import make_small_root
    return make_small_root(tmp_path)
