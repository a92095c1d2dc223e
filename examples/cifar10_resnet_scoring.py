"""BASELINE config 3: CIFAR10 ResNet scoring throughput (the bench.py metric).

Reference pipeline: CNTKModel.transform over the 10k CIFAR test images
with a *downloaded trained model* — per-partition JNI marshalling into
CNTK's C++ eval engine. Here the model is the zoo's TRAINED
``cifar10s_resnet20`` (hash-verified fetch, committed accuracy gate —
`tools/train_zoo_models.py`), the images ship as raw uint8 and are
normalized on device, and the whole path is one jitted forward over
device-resident batches — so the example reports real accuracy, not
random-weight throughput.
"""

import numpy as np

from _common import setup_devices, timed


def main():
    devices = setup_devices()
    import os
    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.models.nn import NNModel
    from mmlspark_tpu.models.zoo import ModelDownloader
    from mmlspark_tpu.testing.datagen import synth_cifar

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    downloader = ModelDownloader(
        os.path.join(repo, ".zoo_cache"), repo=os.path.join(repo, "zoo"))
    meta = downloader.list_models()["cifar10s_resnet20"]
    fn = downloader.load("cifar10s_resnet20")
    print(f"zoo model {meta.name} (trained on {meta.dataset}, "
          f"hash {meta.hash[:12]}...)")

    # full 10k on a real chip; a smaller draw on the CPU test mesh
    n = 2048 if devices[0].platform == "cpu" else 10_240
    images, labels = synth_cifar(n, seed=123_456)   # fresh draw
    df = DataFrame({"image": images})
    scorer = NNModel(model=fn, input_col="image", output_col="scores",
                     batch_size=1024, input_dtype=meta.input_dtype)
    scorer.transform(df.head(1024))  # compile
    with timed() as t:
        out = scorer.transform(df)
    assert out["scores"].shape == (n, 10)
    acc = float((np.asarray(out["scores"]).argmax(1) == labels).mean())
    rate = n / t.seconds / max(len(devices), 1)
    caveat = (" [on the procedural SURROGATE corpus — not real CIFAR-10; "
              "republish via tools/train_zoo_models.py when real files "
              "exist]" if meta.dataset.startswith("synth") else "")
    print(f"resnet20 scoring: {rate:.0f} images/sec/chip "
          f"({len(devices)} device(s)), accuracy={acc:.4f}{caveat}")
    if meta.dataset.startswith("synth"):   # gate matches the corpus
        assert acc > 0.85


if __name__ == "__main__":
    main()
