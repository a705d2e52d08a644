"""Algorithm library — the ml/java + ml/daal + contrib application inventory,
re-built TPU-native. Import the submodule you need; nothing heavy is imported
eagerly (each model compiles its own SPMD program on first use).

Families (reference dirs → modules):
  kmeans (5 comm variants)          → models.kmeans
  sgd/ + experimental daal_sgd      → models.sgd_mf
  daal_cov/pca/mom/qr/svd/...       → models.stats
  daal_linreg/daal_ridgereg         → models.linear
  daal_naive                        → models.naive_bayes
  contrib/mlr                       → models.logistic
  daal_svm + contrib/svm            → models.svm
  daal_knn                          → models.knn
  daal_als (+ _batch)               → models.als
  ccd/ (CCD++ MF)                   → models.ccd (dense bf16 planes, one
                                      layout; prepare / train_prepared;
                                      a fused sweep kernel: ops.ccd_sweep)
  lda/ (CGS) + contrib/lda (CVB0)   → models.lda
  daal_nn                           → models.nn
  daal_optimization_solvers         → models.solvers
  contrib/simplepagerank            → models.pagerank
  wdamds/ (WDA-SMACOF MDS)          → models.mds
  daal_em (GMM)                     → models.em
  daal_quality_metrics              → models.quality
  daal_{stump,adaboost,logitboost,
        brownboost}                 → models.boosting
  daal_dtree/daal_dforest + rf      → models.forest
  daal_ar (association rules)       → models.assoc
  sahad/ + subgraph/ (color coding) → models.subgraph
"""
