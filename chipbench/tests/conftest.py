"""The yardstick's tests run on the CPU, at sizes a test run holds."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

from chipbench import data, spec  # noqa: E402

SMALL = {"num_stations": 9, "years": [1999, 2001], "days_per_year": 3}


@pytest.fixture(scope="session")
def cfg():
    """The one-chip configuration, cut to a test's size."""
    cfg = spec.config(spec.benchmark(), "ghcnd-1m")
    cfg.update(SMALL)
    return cfg


@pytest.fixture(scope="session")
def rec(cfg):
    return data.records(cfg, 2**31 + 12345)


@pytest.fixture(scope="session")
def db(cfg, rec):
    return data.build(cfg, rec)
