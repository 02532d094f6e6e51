"""fluid.install_check.run_check (reference
python/paddle/fluid/install_check.py) — smoke-trains a 2-layer net on the
current device to prove the install works end-to-end."""

from __future__ import annotations

import numpy as np

__all__ = ["run_check"]


def run_check():
    from . import (Executor, Program, default_startup_program, layers,
                   optimizer, program_guard)
    import jax

    main = Program()
    startup = Program()
    with program_guard(main, startup):
        x = layers.data(name="install_check_x", shape=[2], dtype="float32")
        hidden = layers.fc(x, size=4)
        loss = layers.mean(hidden)
        optimizer.SGD(learning_rate=0.01).minimize(loss)

    exe = Executor()
    exe.run(startup)
    out = exe.run(main,
                  feed={"install_check_x": np.ones((2, 2), dtype="float32")},
                  fetch_list=[loss.name])
    # install self-test sanity assert, not a numeric-health path (those
    # route through paddle_tpu.health.detect)
    # resilience: allow
    assert np.isfinite(np.asarray(out[0])).all()
    # observability: allow — user-facing check output
    print("Your paddle_tpu works well on SINGLE device (%s)." %
          jax.default_backend())
    if jax.device_count() > 1:
        from paddle_tpu.parallel import data_parallel  # noqa: F401 (import check)
        # observability: allow — user-facing check output
        print("Your paddle_tpu works well on MULTI devices (%d)." %
              jax.device_count())
    print("install check success!")  # observability: allow
