# The device-calibrated cost model behind backend.execution_plan (costmodel.py).
