"""Traffic files are parameters for the one generator, checked before a
run."""
import glob
import json
import os

import pytest

from chipbench import spec, traffic


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(spec.HERE, "traffic", "*.json"))), ids=os.path.basename)
def test_traffic_files_pass_the_check(path):
    with open(path) as f:
        traffic.check(json.load(f))


@pytest.mark.parametrize("bad", [{"kind": "closed_loop"},
                                 {"kind": "fit"},
                                 {"num_iters": 200}])
def test_check_refuses_bad_traffic(bad):
    with pytest.raises(ValueError):
        traffic.check(bad)
