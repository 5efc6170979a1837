"""The paper's TNN column designs (Table II): ``tnn_columns``."""
