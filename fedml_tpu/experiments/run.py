"""Unified experiment launcher (fed_launch counterpart).

``python -m fedml_tpu.experiments.run --algorithm fedavg --dataset mnist
--model lr --comm_round 20`` — flags mirror the reference mains
(main_fedavg.py:48-120) via the FedConfig argparse bridge.
"""

from __future__ import annotations

import json
import logging
import sys
from typing import Optional, Sequence

from fedml_tpu.core.config import add_args, config_from_args
from fedml_tpu.experiments import ALGORITHMS, run_experiment
from fedml_tpu.utils.compile_cache import enable_compile_cache


def main(argv: Optional[Sequence[str]] = None, default_algorithm: str = "fedavg") -> dict:
    parser = add_args()
    parser.add_argument("--algorithm", type=str, default=default_algorithm,
                        choices=sorted(ALGORITHMS))
    parser.add_argument("--result_json", type=str, default=None,
                        help="write the FULL result dict (history lists "
                             "included) to this path")
    ns = parser.parse_args(argv)
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(filename)s[line:%(lineno)d] %(levelname)s %(message)s",
    )
    algorithm = ns.algorithm
    result_json = ns.result_json
    del ns.algorithm, ns.result_json
    cfg = config_from_args(ns)
    enable_compile_cache()
    result = run_experiment(cfg, algorithm)
    if result_json:
        with open(result_json, "w") as f:
            json.dump({"algorithm": algorithm, **dict(result)}, f)
    printable = {}
    for k, v in dict(result).items():
        if isinstance(v, list) and v and isinstance(v[-1], (int, float)):
            printable[k] = v[-1]          # history series -> final value
        elif isinstance(v, (int, float, str)):
            printable[k] = v
    print(json.dumps({"algorithm": algorithm, **printable}))
    return result


if __name__ == "__main__":
    main(sys.argv[1:])
