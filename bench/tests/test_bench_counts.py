"""The operations and bytes the per-layer metrics divide by, against hand
counts for the paper's CNN."""

import json
from pathlib import Path

import pytest

from bench import refmath
from bench.systems import fedsgd_round

CONFIG = json.loads((Path(__file__).resolve().parents[1] / "configs"
                     / "cnn-static-qpsk.json").read_text())
MODEL = CONFIG["model"]


def test_forward_flops_per_image_match_a_hand_count():
    conv1 = 24 * 24 * 10 * (1 * 5 * 5) * 2
    conv2 = 8 * 8 * 20 * (10 * 5 * 5) * 2
    fc1 = 320 * 50 * 2
    fc2 = 50 * 10 * 2
    assert conv1 + conv2 + fc1 + fc2 == 961_000
    assert refmath.cnn_flops_per_image(MODEL)["forward"] == 961_000


def test_backward_skips_only_the_first_layers_input_gradient():
    flops = refmath.cnn_flops_per_image(MODEL)
    assert flops["backward"] == 961_000 + (961_000 - 288_000)
    assert flops["train"] == flops["forward"] + flops["backward"]


def test_payload_is_the_papers_21840_parameters():
    assert refmath.n_params(MODEL) == 21_840


@pytest.mark.parametrize("clients", [1, 100, 1000])
def test_uplink_bytes_match_a_hand_count(clients):
    assert refmath.uplink_bytes(clients, 21_840) == (
        clients * 21_840 * 4 + 21_840 * 4 + 4 * clients)


def test_round_work_scales_with_the_cohort():
    traffic = {"clients": 1000, "batch_per_round": 32, "test_per_class": 100}
    work = fedsgd_round.round_work(CONFIG, traffic)
    assert work["train_flops"] == 1000 * 32 * (961_000 + 1_634_000)
    assert work["eval_flops"] == 1000 * 961_000
    assert work["uplink_bytes"] == 1000 * 21_840 * 4 + 21_840 * 4 + 4000
