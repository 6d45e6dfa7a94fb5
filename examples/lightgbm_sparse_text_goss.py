"""LightGBM - Text-Scale Sparse Training with GOSS Sampling.

The regime the reference's CSR path exists for (generateSparseDataset ->
LGBM_DatasetCreateFromCSRSpark, lightgbm/TrainUtils.scala:23-66): hashed
text features far too wide to densify, trained end to end from raw text.
The journey: tokenize -> hashTF into a 2^15-wide sparse space ->
LightGBMClassifier with GOSS (gradient-based one-side sampling, the
engine's headline speed feature — exact top-k selection + selected-row
nnz compaction, so the sampled fit touches only the selected rows'
nonzeros) -> evaluate -> save/reload.
"""

import os
import tempfile

import numpy as np

from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.featurize.text import TextFeaturizer
from mmlspark_tpu.gbdt import LightGBMClassifier
from mmlspark_tpu.gbdt.stages import LightGBMClassificationModel


def main():
    rng = np.random.default_rng(3)
    positive = ["refund", "broken", "terrible", "slow", "crash"]
    neutral = ["the", "a", "product", "device", "today", "ordered",
               "shipment", "box", "arrived", "screen", "cable", "blue"]
    texts, labels = [], []
    for _ in range(3000):
        words = list(rng.choice(neutral, size=12))
        complaint = rng.random() < 0.5
        if complaint:
            words[rng.integers(0, len(words))] = str(
                rng.choice(positive))
        rng.shuffle(words)
        texts.append(" ".join(words))
        labels.append(float(complaint))
    df = DataFrame.from_dict({"text": np.array(texts, object),
                              "label": np.array(labels)},
                             num_partitions=4)

    # tokenize -> hashTF (2^15 features: sparse rows, never densified)
    feats = TextFeaturizer(inputCol="text", outputCol="features",
                           numFeatures=1 << 15, useIDF=False)
    train_df = feats.fit(df).transform(df)

    # GOSS: exactly top 20% |gradient| rows + 10% sampled others per
    # iteration; sparse rows auto-route to the CSR engine
    clf = LightGBMClassifier(
        boostingType="goss", topRate=0.2, otherRate=0.1,
        numIterations=40, numLeaves=15, minDataInLeaf=10,
        labelCol="label")
    model = clf.fit(train_df)
    pred = np.array([float(p) for p in
                     model.transform(train_df).column("prediction")])
    acc = float((pred == np.array(labels)).mean())
    print(f"sparse GOSS train accuracy: {acc:.3f}")
    assert acc > 0.9, acc

    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "complaint_model")
        model.save(path)
        reloaded = LightGBMClassificationModel.load(path)
        pred2 = np.array([float(p) for p in
                          reloaded.transform(train_df).column("prediction")])
        assert (pred2 == pred).all()
    print("saved + reloaded: predictions identical")
    print("EXAMPLE OK")


if __name__ == "__main__":
    main()
