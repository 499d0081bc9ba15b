"""Quickstart: the paper's pipeline in 60 seconds on CPU.

1. run a miniature Rayleigh-Taylor simulation (real spectral solver),
2. compress its fields with the error-bounded TPU-adapted ZFP codec,
3. find the safe tolerance with Algorithm 1 (no retraining),
4. train a few steps of the DCGAN-backbone surrogate on the compressed data.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax.numpy as jnp

from repro.compression import get_codec
from repro.core import CompressedArrayStore, find_tolerance
from repro.models.surrogate import FieldNormalizer, SurrogateConfig, make_conditions
from repro.sim import SimParams, run_simulation
from repro.train.loop import TrainConfig, train_surrogate


def main():
    print("== 1. simulate (Boussinesq spectral RT, 48x16, 11 snapshots)")
    fields = np.asarray(run_simulation(SimParams(atwood=0.5, amplitude=0.03),
                                       ny=48, nx=16, nsteps=400, nsnaps=11))
    print(f"   fields: {fields.shape}, density in [{fields[..., 0].min():.2f}, "
          f"{fields[..., 0].max():.2f}]")

    print("== 2. error-bounded compression")
    sample = jnp.asarray(np.transpose(fields[5], (2, 0, 1)))
    codec = get_codec("fixed_accuracy")
    for tol in (1e-1, 1e-2):
        cf = codec.encode_batch(sample[None], jnp.asarray([tol], jnp.float32))
        err = float(jnp.max(jnp.abs(codec.decode_batch(cf)[0] - sample)))
        ratio = sample.size * 4 / int(np.asarray(codec.nbytes(cf))[0])
        print(f"   tol={tol:g}: max_err={err:.2e} (bound holds: {err <= tol}) "
              f"ratio={ratio:.1f}x")

    print("== 3. Algorithm 1 (model-centric tolerance, no retraining)")
    res = find_tolerance(np.asarray(sample), model_l1_error=0.05)
    print(f"   tolerance={res.tolerance:.3g} ratio={res.ratio:.1f}x "
          f"iterations={res.iterations} (paper: converges in 1-2)")

    print("== 4. train surrogate on online-decompressed data (20 steps)")
    norm = FieldNormalizer.fit(fields)
    nf = np.asarray(norm.normalize(jnp.asarray(fields)))
    samples = [np.transpose(x, (2, 0, 1)) for x in nf]
    store = CompressedArrayStore(samples, tolerances=[res.tolerance] * len(nf))
    cond = make_conditions(np.tile(SimParams().as_vector(), (1, 1)), 11)
    cfg = SurrogateConfig(height=48, width=16, base_channels=16)
    tc = TrainConfig(epochs=20, batch_size=8, lr=1e-3, log_every=5)
    _, losses = train_surrogate(
        cfg, tc, cond,
        lambda i: jnp.transpose(store.get_batch(i), (0, 2, 3, 1)), len(nf))
    print(f"   losses: {[(s, round(l, 3)) for s, l in losses[:6]]}")
    print(f"   store ratio {store.ratio:.1f}x, "
          f"decode throughput {store.stats.throughput_mbs():.0f} MB/s")
    print("done.")


if __name__ == "__main__":
    main()
