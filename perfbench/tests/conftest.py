"""One throw-away checkout with the shelved cells' entries added, shared by
the tests that rehearse them."""

import pytest

from common import add_shelved_cells, checkout


@pytest.fixture(scope="session")
def shelved_root(tmp_path_factory):
    return str(checkout(tmp_path_factory.mktemp("shelved"),
                        add_shelved_cells))
