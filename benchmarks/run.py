"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit).

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run fig7 fig12 # subset
"""

import sys

from benchmarks import (fig2_component_speedup, fig7_throughput_onprem,
                        fig8_throughput_aws, fig9_pp_comparison,
                        fig10_gpu_ratios, fig11_homogeneous, fig12_asym_ea,
                        table3_utilization)

BENCHES = {
    "fig2": fig2_component_speedup.main,
    "fig7": fig7_throughput_onprem.main,
    "fig8": fig8_throughput_aws.main,
    "fig9": fig9_pp_comparison.main,
    "fig10": fig10_gpu_ratios.main,
    "fig11": fig11_homogeneous.main,
    "fig12": fig12_asym_ea.main,
    "table3": table3_utilization.main,
}


def main() -> None:
    which = sys.argv[1:] or list(BENCHES)
    print("name,us_per_call,derived")
    for name in which:
        BENCHES[name]()


if __name__ == "__main__":
    main()
