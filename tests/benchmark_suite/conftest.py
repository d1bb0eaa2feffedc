import pytest

from bm_util import root_with_serve_cell


@pytest.fixture(scope="session")
def serve_root(tmp_path_factory):
    return root_with_serve_cell(tmp_path_factory.mktemp("with_serve_cell"))
