"""The seeded weights are row-balanced exactly: every row of W_x and W_h
keeps the configuration's count of non-zeros, so the program's
magnitude prune keeps every one of them and serves the reference's
model."""
import numpy as np

import bench_tiny  # noqa: F401  (puts the repo on the path)
from bench.lib import cost, registry, weights


def test_every_row_keeps_exactly_its_count():
    cfg = registry.config(registry.benchmark(), "lstm_timit")
    # this seed's uniform draws tie at the 38th largest of one W_x row,
    # which a threshold on the draws kept as a 39th non-zero
    params = weights.make_params(cfg, 3000000411)
    for lp, d in zip(params["layers"], cost.layer_dims(cfg)):
        assert (np.count_nonzero(np.asarray(lp["w_x"]), axis=1)
                == d["kx"]).all()
        assert (np.count_nonzero(np.asarray(lp["w_h"]), axis=1)
                == d["kh"]).all()
