"""Entry point: python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>."""
import sys
import time

_T_MAIN = time.time()  # "main entered": before any heavy import


def main(argv=None) -> int:
    from chipbench import harness

    return harness.main(argv, t_main=_T_MAIN)


if __name__ == "__main__":
    sys.exit(main())
