"""Run in a child process with four CPU devices: a sound tiny ring4 run,
then one with the exchange between chips left out (every ppermute hands a
node its own payload back).  Prints one JSON line per run."""
import json
import sys
import tempfile

import tiny


def main() -> None:
    import jax
    c = tiny.cell("ring4.q2")
    out = {}
    with tempfile.TemporaryDirectory() as d:
        out["sound"] = tiny.run(c, 2_200_000_003, d)
        jax.lax.ppermute = lambda x, axis_name, perm: x
        out["no_exchange"] = tiny.run(c, 2_200_000_003, d)
    for k, r in out.items():
        print(json.dumps({"run": k, "correct": r["correct"],
                          "compared": r["compared"]}))
    sys.stdout.flush()


if __name__ == "__main__":
    main()
