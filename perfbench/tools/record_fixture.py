"""Record the small trace the tests of xplane.py read (run once on the chip;
the file is kept at perfbench/testdata/small.xplane.pb): three executions of
one small program, each inside a ``train:step`` span, 20 ms apart.

    python3 -m perfbench.tools.record_fixture <out_dir>
"""

import sys
import time


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    from perfbench import chipside

    @jax.jit
    def small_program(x):
        for _ in range(4):
            x = jnp.tanh(x @ x) * 0.01
        return x.sum()

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    small_program(x).block_until_ready()
    tracer = chipside.Tracer(out_dir)
    tracer.start()
    for _ in range(3):
        with chipside.annotate("train:step"):
            small_program(x).block_until_ready()
        time.sleep(0.02)
    tracer.stop()
    print(tracer.result(), jax.devices())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
