"""Record the small chip trace that test_chipbench_trace.py (beside this
file) reads.

    python chipbench/tests/record_trace.py   # on one TPU v5e

Runs deepseek-7b-pp2 cut to 2 layers under the chat mix at 0.5 req/s for
a 3-second traced window and writes the raw trace under
chipbench/tests/data/.
"""

import dataclasses
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from chipbench import bench, generator  # noqa: E402

OUT = HERE / "data"


def main():
    cell = bench.load_cell("deepseek-7b-pp2.longgen")
    conf = dict(cell.conf, num_hidden_layers=2)
    mix = dict(generator.load_mix("chat"), preroll_s=2)
    cell = dataclasses.replace(cell, name="deepseek-7b-pp2.chat", conf=conf,
                               mix=mix, params={"rate": 0.5, "limits": {
                                   "max_logit_gap": 0.5}})
    out = bench.run(cell.name, 5, 3.0, True, cell=cell,
                    keep_trace=OUT / "deepseek_2layer_chat.xplane.pb")
    print(json.dumps(out))


if __name__ == "__main__":
    main()
