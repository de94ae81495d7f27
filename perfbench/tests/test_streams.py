"""The generator's streams do not move: every column of each cell's stream,
at its traffic file's ``base_seed`` and one fixed run seed, hashes as it
did when the cells' bounds and limits were set.  A new jobs-profile or
traffic key must leave the streams of files that do not use it alone."""
import hashlib
import json
import os

import numpy as np
import pytest

import streams
from conftest import BENCH

RUN_SEED = 4_000_000_123

#: sha256 of each column's dtype name and bytes
GOLDEN = {
    "helios-flash": {
        "runtime":
            "fdd17b31e2c731c091113ac6883eff2317f6a3eb44a14ec2bbd2dd77254b2643",
        "est":
            "5c5d5acd2197abbbf9771291638037687663925b7caa440dd71e567fb6f41fc5",
        "user":
            "9f99c1f5753e6a862668cc8fbdc24ddca3a36a974c358d1696e587e0431a70d8",
        "gpus":
            "bb2a9d7f212e91932c44e0887000d5efbc2336705d0d917dab1257b143dbd43c",
        "gpu_type":
            "d83b90925ad9a98db0e148cfb2096c1be786bdb6a38871de89caa15110ca536c",
        "vc":
            "b8c87d06af07ec689398286674f0b0d2ff9c005bd70e9fddeeca5c0d21507283",
        "submit":
            "ef5dcc5f2390b18b405fa40d30b01cd60e261204c533903bb87e5980c3e2984d",
        "job_id":
            "e52bd7d1620eb8d9f554c5b77fe9d138c6f1a6ba072cfd45785df0db0ebe5e36",
    },
    "philly-gang": {
        "runtime":
            "b67abf85310374fd4f2d78fbd26df98e85ecb65c233ec3ff214aeedaa83b4c14",
        "est":
            "dd22a651f0334216d1a8ae338b9d47c039309704dc3fae39668f89233737f75c",
        "user":
            "fa15ba16058ca93b56d4ac257497a90e0a2a2302e7b32df1f07690918240f2c7",
        "gpus":
            "b3da920da43da9de863d34bd7adb1109c17a2f71db5e1aed7c9b21475cdc98d8",
        "gpu_type":
            "2b98f27fa798929a2857e21eb6c0ade63609455170c830e0bd45747b7e4fa0cf",
        "vc":
            "541fc6d3fb0b0bb67b9a83123178fb6c4a5d9b4c811d4c1d4216d5fbcc1315e7",
        "submit":
            "7db0bcfe1adadf07834a98b0a3fa17af534825c6df750b599a6936f099e8094e",
        "job_id":
            "c4941ecbe8ba19f8803b56dcee47461af869afd623df08ab024fddcb68aa50b4",
    },
    "helios-light": {
        "runtime":
            "c3674fc60f290226a7d928773043045a70adcd3de608880cec999d1da84b101e",
        "est":
            "c9686128b67adfd31d5e2ecb365df316bc26aa2139f5de282cb22b83bc9db851",
        "user":
            "3c257b26c3454f65054061a86248592cfa6fb35a5fb5cb9641e746fcb8989eed",
        "gpus":
            "936eab4b4a59b2bbdb15a54c389e7beb09827688e9c1cf237c9cc1c4912ee79c",
        "gpu_type":
            "003c783235e0ad8841c8b1dc5954d912c1fc149529bd40e62cbb79d39d26622d",
        "vc":
            "e9802b2fafbf421ba77a6ccb885089e245d975eccce01d261e32d9301a7abca0",
        "submit":
            "5c9ac8533cc55139db3b8572efee267aa4d371c40428e9c6384ea81fba6f483f",
        "job_id":
            "6dcd326e453f343c3a46bc0fbfd9492b19724160cb7d0b797fea750e428b9c28",
    },
}


def _load(path):
    with open(path) as f:
        return json.load(f)


def _cell_stream(name):
    root = os.path.dirname(BENCH)
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    cell = {w["name"]: w for w in bench["workloads"]}[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    prof = _load(os.path.join(root, entry["file"]))["jobs"]
    traffic = _load(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    return streams.stream_columns(prof, traffic, RUN_SEED)


def _digest(col):
    col = np.ascontiguousarray(col)
    return hashlib.sha256(str(col.dtype).encode() + col.tobytes()).hexdigest()


@pytest.mark.parametrize("cell", sorted(GOLDEN))
def test_stream_columns_are_unchanged(cell):
    cols = _cell_stream(cell)
    assert {k: _digest(v) for k, v in cols.items()} == GOLDEN[cell]


def test_vc_share_draws_the_stated_demand():
    prof = _load(os.path.join(BENCH, "configs", "helios-rltune.json"))["jobs"]
    traffic = _load(os.path.join(BENCH, "traffic", "helios-flash.json"))
    share = [[0, 0.55], [1, 0.25], [2, 0.12], [3, 0.08]]
    cols = streams.stream_columns(dict(prof, vc_share=share), traffic,
                                  RUN_SEED)
    got = np.bincount(cols["vc"], minlength=4) / cols["vc"].size
    assert got.size == 4
    np.testing.assert_allclose(got, [s for _, s in share], atol=0.01)
