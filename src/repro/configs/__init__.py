"""The paper's synthetic study configurations: ``dml_synthetic`` (DML,
Fig. 6), ``iv_synthetic`` (OrthoIV / DRIV) and ``sweep_synthetic`` (the
E = 64 segment sweep).  Import the module for its config."""
